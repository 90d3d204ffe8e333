"""sfprof — per-kernel cost ledger reports and a bench regression gate.

The runtime layer (``spatialflink_tpu/telemetry.py``) records the raw
signals: spans, device-boundary bytes, recompile events, the per-(kernel,
signature) runtime table with lazily captured XLA cost analysis, and
compaction bucket picks. ``telemetry.write_ledger`` freezes one run of
those signals into a schema-versioned JSON document; this package turns
ledgers into decisions:

- ``python -m tools.sfprof report <ledger|trace>`` — phase attribution
  per operator (assemble/ship/compute/fetch from the span nesting, with
  the unattributed residue reported explicitly — no silently missing
  time), top kernels by dispatch time / compiles / flops, bytes per
  window, host-gap detection between window spans.
- ``python -m tools.sfprof diff <A> <B> [--gate]`` — per-metric deltas
  with per-entry tolerance bands (wide EPS bands until per-cell spreads
  are measured on the chip). ``--gate`` exits nonzero on regression so
  CI can gate.
- ``python -m tools.sfprof health <ledger>`` — threshold verdicts on
  recompile churn, overflow counters, late drops, watermark-lag max,
  and dropped trace events; the post-bench check next to
  ``python -m tools.sfcheck``.

Modules: ``ledger`` (load + schema validation), ``attribution`` (span
tree → phase breakdown), ``cli`` (the subcommands).
"""
