"""On-chip benchmark of the index-serving path (see ``run.py``).

Everything a cell needs is found by name: ``BENCHMARK.json`` names the cell,
its configuration (``configs/<name>.json``), its traffic mix
(``traffic/<name>.json``) and its per-layer metrics
(``metrics/<name>.py``).  The harness imports only the system under test
(``repro.index``) and its spans and counters; the corpus generator, the
traffic generator, the reference and the trace reduction live here.
"""
