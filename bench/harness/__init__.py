"""The benchmark harness: one general harness for every cell.

Everything that belongs to one configuration, traffic mix, plain
reference or per-layer metric lives in a file of its own under
`bench/`, found by the name `BENCHMARK.json` gives it:

  bench/configs/<config>.json      deployment sizes, source, guarantees
  bench/traffic/<traffic>.json     parameters of the one generator
  bench/reference/<problem>.py     plain reference of a decision rule
  bench/metrics/<metric>.py        reader of one per-layer metric
  bench/peaks.json                 chip peaks keyed by device kind

so adding a cell, mix, configuration or metric adds files and entries
and edits none.
"""
