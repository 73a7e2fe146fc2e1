"""Operations and bytes of the fold model and of its kernels, from shapes.

``ppm`` counts a whole fold's matrix-multiply FLOPs at its real length (the
model's work, the same under every scheme).  ``kernels`` lists the calls
one served batch makes to each kernel, with the operations and the least
bytes each call must move, from which a kernel's roofline time follows.
"""
