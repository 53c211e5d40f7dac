"""The chip benchmark: cells named in BENCHMARK.json, run through SRServer.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its configuration in
``bench/configs/<config>.json`` (its ``server`` keys go to
``SRServer.open`` as they are), its traffic mix in
``bench/traffic/<traffic>.json``, the generator the mix names in
``bench/loops/<loop>.py``, and each metric's reader in
``bench/metrics/<metric>.py``.  A new cell, mix, loop or metric is new
files plus new entries in BENCHMARK.json; no file here needs an edit.
"""
