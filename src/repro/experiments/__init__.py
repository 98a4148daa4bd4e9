"""Experiment composition: workload grids x seeds x execution options.

An :class:`Experiment` collects labeled ``repro.workloads.Workload`` specs
(or whole cartesian grids of them), then runs everything as one deduped
batched sweep — one compile per ``(alg, T, N, K, n_events)`` shape bucket,
per-seed error bars, results addressable by label or by spec:

>>> from repro.experiments import Experiment, ExecOptions
>>> from repro.workloads import Workload
>>> exp = (Experiment("demo", n_seeds=2, n_events=1500,
...                   options=ExecOptions(backend="xla"))
...        .add_grid(Workload("alock", 2, 2, 8), locality=(0.85, 1.0)))
>>> res = exp.run()
>>> res.labels
['locality0.85', 'locality1']
>>> res["locality1"].mean_mops >= res["locality0.85"].mean_mops
True

``ExecOptions`` is the immutable how-to-execute value (backend, device
sharding, chunking) threaded explicitly through the benchmark suite —
there is no process-wide execution state.

Named scenario programs live in the registry (``run_scenario`` /
``scenario_names``) — the single entry point behind
``benchmarks.run --scenario``. A scenario
can carry an :class:`Slo` (simulated-p99 ceiling, wall-clock events/sec
floor); ``benchmarks.run --check-slo`` evaluates it with
:func:`check_slo` and gates CI on the result.
"""
from repro.experiments.experiment import Experiment, ExperimentResult
from repro.experiments.options import ExecOptions
from repro.experiments.registry import (Scenario, fig5_workloads,
                                        get_scenario, run_scenario,
                                        scenario, scenario_names,
                                        scenario_workloads)
from repro.experiments.slo import Slo, SloReport, check_slo

__all__ = [
    "ExecOptions", "Experiment", "ExperimentResult", "Scenario", "Slo",
    "SloReport", "check_slo", "fig5_workloads", "get_scenario",
    "run_scenario", "scenario", "scenario_names", "scenario_workloads",
]
