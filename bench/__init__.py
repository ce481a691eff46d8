"""The chip benchmark of this repository.

``BENCHMARK.json`` at the repository root names the cells; everything a
cell needs is found by name under this directory:

  configs/<config>.json      model sizes (published keys), weight format,
                             correctness limit
  traffic/<traffic>.json     parameters of the load generator
  metrics/<metric>.py        one per-layer metric each (``compute(run)``)
  references/<name>.py       plain float32 reference a config names
  peaks.json                 chip peaks by ``device_kind``

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once on the accelerator and prints one JSON
line.  Adding a configuration, traffic mix or metric is adding files and
``BENCHMARK.json`` entries; no existing file changes.
"""
