"""The chip benchmark: one harness, driven by the files under this directory.

`bench/run.py` runs one cell of `BENCHMARK.json`. Everything that belongs to
one configuration, traffic mix or per-layer metric lives in a file of its
own, found by the name `BENCHMARK.json` gives it:

- `bench/configs/<config>.json`: the configuration as it is run, naming its
  kind (today `explore`);
- `bench/traffic/<traffic>.json`: the parameters of the traffic mix;
- `bench/kinds/<kind>.py`: the one driver of each kind;
- `bench/metrics/<metric>.py`: one reader from a run's record to a value;
- `bench/reference/`: the plain references that decide `correct`;
- `bench/work.py` and `bench/peaks.json`: analytic operation and byte
  counts, and the chip's published peaks.
"""
