"""On-chip serving benchmark for BlockEngine, driven by BENCHMARK.json.

Each configuration (``configs/<name>.json``), traffic mix
(``traffic/<name>.json``) and per-layer metric (``metrics/<name>.py``)
lives in a file of its own and is found by the name a cell gives it; a
configuration's architecture (``archs/<model_type>.py``) by its
``model_type``.
"""
