"""Execution options as an explicit immutable object.

``ExecOptions`` carries everything about *how* a sweep executes — backend,
device sharding, chunking — as one frozen value that callers thread
explicitly through the benchmark suite and ``Experiment.run``. It replaces
the old mutable ``benchmarks/common.py::EXEC`` module global, whose state
leaked between test runs and benchmark sections.
"""
from __future__ import annotations

import os
from dataclasses import dataclass

_BACKENDS = ("auto", "xla", "pallas")


@dataclass(frozen=True)
class ExecOptions:
    """How to execute a sweep: (backend, devices, chunk), immutably.

    backend: "auto" | "xla" | "pallas" — per-replica engine
      (``sim.resolve_backend`` semantics).
    devices: shard sweep buckets over the first N JAX devices (mesh axis
      "data"); None keeps the single-dispatch layout.
    chunk: rows per device per dispatch *unit*; a dispatch coalesces as
      many units as fit one kernel tile per device
      (``core/batch.py``, ``dispatch_rows``).
    """
    backend: str = "auto"
    devices: int | None = None
    chunk: int | None = None

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, got "
                             f"{self.backend!r}")
        for name in ("devices", "chunk"):
            v = getattr(self, name)
            if v is not None:
                v = int(v)
                if v < 1:
                    raise ValueError(f"{name} must be >= 1, got {v}")
                object.__setattr__(self, name, v)

    @classmethod
    def from_env(cls, **kw) -> "ExecOptions":
        """Defaults with ``REPRO_BACKEND`` honored; non-None kwargs
        override (an explicit ``backend=None`` means "not given on the
        CLI", so the env var still applies)."""
        kw = {k: v for k, v in kw.items() if v is not None}
        kw.setdefault("backend", os.environ.get("REPRO_BACKEND", "auto"))
        return cls(**kw)

    def device_list(self):
        """The resolved device list for ``batch.sweep(devices=)``."""
        if self.devices is None:
            return None
        import jax
        devs = jax.devices()
        if self.devices > len(devs):
            raise ValueError(f"devices={self.devices} but only {len(devs)} "
                             f"JAX device(s) are visible")
        return devs[:self.devices]

    def sweep_kwargs(self) -> dict:
        """Keyword arguments for ``repro.core.batch.sweep``."""
        return {"backend": self.backend, "devices": self.device_list(),
                "chunk": self.chunk}
