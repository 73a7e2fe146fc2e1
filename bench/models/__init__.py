"""Fold models the benchmark serves: ``bench/models/<model>.py``, found by
the ``"model"`` key of a configuration file (``bench.run.model_of``).

A model module defines exactly these names:

* ``program_config(cfg)``: the program's config object for the file;
* ``make_client(cfg, traffic, params)``: the client the program serves
  the cell through (``bench.run.serve_args`` and ``program_scheme`` give
  the serving settings and the AAQ site table every fold model shares);
* ``make_params(cfg, seed)``: the weights from the seed, made on the
  device, in the layout the program loads;
* ``Reference``: the plain reference, ``Reference(cfg, dtype="float32")
  (params, request, bucket) -> (coords, distogram)``; it imports nothing
  of the program;
* ``fold_flops(cfg, n)``: a whole fold's FLOPs at real length ``n``;
* ``init_program_params(key, cfg)``: the program's own initialisation,
  whose layout ``make_params`` has to match;
* ``TINY``: the path of its configuration at a CPU test size.

A request is whatever the cell's traffic generator makes (for
``backlog``, one sequence); the harness hands it untouched to the client
and to the reference.
"""
