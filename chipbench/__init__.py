"""On-chip benchmark of the serving path: one harness driven by data.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: one model
configuration under one traffic mix.  Everything that belongs to one of
them lives in a file of its own, found by name:

  configs/<config>.json     the configuration as it is run: widths, source,
                            ``reduced``, ``assumed``, the name of its plain
                            reference and the limit of each number compared;
                            the keys that name fields of the program's
                            ``ModelConfig`` are handed to the program
  references/<name>.py      that plain float32 reference (``logits_at``)
  traffic/<traffic>.json    loop type, rate, lengths, TTL, how many
                            requests the check compares
  metrics/<metric>.py       one reader per metric: ``read(run)`` returns a
                            number or None when the run has nothing to read
  peaks.json                published peaks keyed by ``device_kind``

Adding a cell, a configuration, a traffic mix or a metric is adding such
files and a ``BENCHMARK.json`` entry; no file here needs an edit.

The code kept here is the yardstick: arrival schedules (``arrivals``),
operation and byte counts (``counts``), the trace reduction (``tracing``),
the benchmark's own weights (``weights``) and the comparison that decides
``correct`` (``check``).  From the program the harness takes only the
system under test, its phase timers and its serve statistics; it hands the
program the configuration and the initialisation of the weights.
"""
