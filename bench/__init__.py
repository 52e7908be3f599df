"""Chip benchmark of the RollMux reproduction, driven by ``BENCHMARK.json``.

One run measures one cell (a configuration under a traffic mix) on the
accelerator the process finds:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the configuration in
``bench/configs/<name>.json``, its model family in
``bench/families/<model_type>.py`` (the file's published ``model_type``),
the traffic mix in ``bench/traffic/<name>.json``, the reader of each
per-layer metric in ``bench/metrics/<name>.py`` and the limits of the
output check in ``bench/limits/<cell>.json``.  A traffic file names its
``kind``, the module under ``bench/kinds/`` that drives the program with
it.
"""
