"""Static jaxpr lint for the event-engine: trace, don't run.

``repro.analysis`` traces the engine's public entrypoints to closed
jaxprs (``jax.make_jaxpr`` — abstract evaluation, nothing executes, no
TPU needed) and evaluates a rule registry over the equation graphs.
Four rule families ship (see ``docs/analysis.md`` for the catalog):

  * **mosaic-lowerability** (M001-M003) — the Pallas kernel must stay
    free of 64-bit avals, dynamic scatter/gather and 1-D iota, the three
    things Mosaic rejects;
  * **x64-cleanliness** (X001) — the pairs path holds a zero-int64
    contract with x64 disabled;
  * **retrace-hazards** (R001, R003) — weak_type operands, >1 abstract
    signature per sweep bucket;
  * **vmem-consistency** (V001) — ``vmem.buffer_table`` must mirror the
    buffers the traced ``pallas_call`` actually binds.

CLI: ``python -m repro.analysis`` (report), ``--strict`` (exit 1 on any
finding — the CI lint leg), ``--selftest`` (run the known-bad fixture
corpus), ``--imports`` (import-graph dead-weight report).

>>> from repro.analysis import Finding, RULES
>>> sorted(RULES)
['M001', 'M002', 'M003', 'R001', 'R003', 'V001', 'X001']
>>> print(Finding("M001", "mosaic-lowerability", "error",
...               "pallas-native:demo", "pallas_call @ k.py:1",
...               "int64 aval inside the kernel").format())
M001 (mosaic-lowerability, error) pallas-native:demo [pallas_call @ k.py:1]
      int64 aval inside the kernel
"""
from repro.analysis.entrypoints import (Entrypoint, collect_buckets,
                                        trace_entrypoints)
from repro.analysis.rules import (RULES, Finding, Rule, bucket_signature,
                                  check_bucket_signatures,
                                  check_vmem_consistency, rule, run_rules)
from repro.analysis.walk import EqnSite, all_avals, eqn_src, walk_jaxpr

__all__ = [
    "Entrypoint", "trace_entrypoints", "collect_buckets",
    "Finding", "Rule", "RULES", "rule", "run_rules",
    "bucket_signature", "check_bucket_signatures", "check_vmem_consistency",
    "EqnSite", "walk_jaxpr", "all_avals", "eqn_src",
]
