"""Chip benchmark of the LMB serve path: one cell per run, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Each configuration, traffic mix,
per-layer metric and correctness limit is a file of its own, found by
name:

- ``bench/configs/<config>.json``   model sizes as run, with the source,
  and the architecture module that knows the model (key ``arch``);
- ``bench/arch/<arch>.py``          one module per architecture: the
  stated config, seeded weights, the float32 reference, FLOP accounts;
- ``bench/traffic/<traffic>.json``  the parameters of one traffic mix;
- ``bench/metrics/<metric>.py``     one reader per per-layer metric;
- ``bench/limits/<cell>.json``      the correctness limit of one cell;
- ``bench/peaks.json``              device peaks keyed by ``device_kind``.
"""
