"""The chip benchmark: ``python3 bench/run.py --workload <cell> ...``.

Layout, each part found by name from ``BENCHMARK.json``:

- ``harness.py``: set-up, the measured window, the check, the result line;
- ``drivers/<kind>.py``: how a cell of that kind calls the program;
- ``workloads/<cell>.json``: a cell's traffic, driver kind and limits;
- ``configs/<config>.json``: a configuration, with its source and cuts;
- ``metrics/<metric>.py``: one metric's reader;
- ``reference/``: the plain references the checks compare with;
- ``peaks.py``, ``counts.py``, ``tracereduce.py``: the yardstick;
- ``control.py``: the lower-precision control of the engine cells' check.
"""
