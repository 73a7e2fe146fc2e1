"""Operations and bytes of the fold models and of their kernels, from shapes.

``ppm`` counts a whole ESMFold fold's matrix-multiply FLOPs at its real
length (the model's work, the same under every scheme); the harness
reaches it only as ``fold_flops`` of ``bench/models/esmfold_ppm.py``, and
another model's count sits in a file of its own here.  ``kernels`` gives
the operations and the least bytes of each traced call of a kernel, from
which its roofline time follows; a later kernel's work function sits in a
new file here and its metric passes it to ``kernels.roofline_share``.
"""
