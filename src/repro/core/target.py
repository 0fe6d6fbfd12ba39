"""Target: a structured, hashable description of the execution substrate.

The paper's flow generates an accelerator *for a device*: the back-end
lowers the algorithm against a hardware description (HBM channel count,
URAM budget, pipeline replication factor) once, and the resulting artifact
is deployed. This module is that hardware description re-targeted at the
JAX substrate: everything the lowering needs to know about *where* the
program will run — and nothing about *what* the program computes.

``Target`` absorbs the loose layout/placement fields that used to live on
:class:`~repro.core.options.CompileOptions` (``burst``/``cache``/
``shuffle``/``compact_frontier``/``pallas``/``n_partitions``/
``interpret``); ``CompileOptions`` now carries only front-end / middle-end
concerns (the pass pipeline and compile-time scalar bindings) plus a
compat shim that maps the old kwargs onto ``Target`` overrides.

The split is what makes :class:`~repro.core.accelerator.Accelerator`
artifacts well-defined: ``program.lower(target, shape)`` AOT-compiles
every kernel against (target, shape-bucket) and the result is valid for
*any* graph of that shape on that substrate —

    target  = Target()                          # local, all optimizations
    acc     = program.lower(target, shape=GraphShape(n_vertices=2000,
                                                     n_edges=16000))
    session = acc.bind(graph)                   # shape check only

``Target`` is a frozen dataclass: hashable, usable as a cache key, and
``repr``-stable for content fingerprinting.
"""
from __future__ import annotations

from dataclasses import dataclass, fields, replace
from typing import Optional, Tuple

#: Target fields that CompileOptions used to own; the CompileOptions compat
#: shim accepts these as kwargs and maps them to ``target_overrides``.
LEGACY_OPTION_FIELDS: Tuple[str, ...] = (
    "burst",
    "cache",
    "shuffle",
    "compact_frontier",
    "pallas",
    "n_partitions",
    "interpret",
)

_KINDS = ("local", "distributed")
_DTYPE_POLICIES = ("fp32",)  # the device ABI this reproduction lowers to


@dataclass(frozen=True)
class Target:
    """Execution-substrate description (the accelerator's hardware side).

    Backend placement:

    * ``kind`` — ``"local"`` (one device, the paper's single-accelerator
      system) or ``"distributed"`` (shard_map + all_to_all shuffle
      supersteps across a device mesh).
    * ``n_devices`` / ``axis`` — mesh shape for distributed targets
      (``0`` = every visible device).

    Memory-access optimizations (paper §III-C3, formerly CompileOptions):

    * ``burst`` — partitioned, ascending-src streaming order.
    * ``cache`` — hub-vertex relabeling (dense VMEM-prefix hub cache).
    * ``shuffle`` — stream edges in dst order (the burst order stably
      sorted by dst) and commit dst-lane writes as sorted segment
      reductions, with no runtime permutation (conflict-free).
    * ``compact_frontier`` — only traverse active edges when the frontier
      is small (direction optimization).
    * ``pallas`` — route scatter-reduce/gather through Pallas TPU kernels.
    * ``n_partitions`` — dst-range partition count (0 = auto from
      ``partition_vertices``).
    * ``partition_vertices`` — VMEM sizing unit: auto-partitioning targets
      one dst-range slice of about this many vertices per partition (the
      analogue of sizing a subpartition to URAM).
    * ``interpret`` — Pallas interpret mode (None = auto: interpreted
      unless a real TPU backend is present).
    * ``dtype_policy`` — device number format policy; ``"fp32"`` is the
      only ABI this reproduction lowers (int32/float32/bool buffers).
    """

    kind: str = "local"
    n_devices: int = 0
    axis: str = "data"
    burst: bool = True
    cache: bool = True
    shuffle: bool = True
    compact_frontier: bool = True
    pallas: bool = False
    n_partitions: int = 0
    partition_vertices: int = 4096
    interpret: Optional[bool] = None
    dtype_policy: str = "fp32"

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(
                f"unknown Target.kind {self.kind!r}; expected one of {_KINDS}"
            )
        if self.dtype_policy not in _DTYPE_POLICIES:
            raise ValueError(
                f"unsupported dtype_policy {self.dtype_policy!r}; this "
                f"back-end lowers {_DTYPE_POLICIES} (int32/float32/bool buffers)"
            )
        if self.n_devices < 0:
            raise ValueError("n_devices must be >= 0 (0 = all visible devices)")
        if self.partition_vertices < 1:
            raise ValueError("partition_vertices must be >= 1")
        if self.n_partitions < 0:
            raise ValueError("n_partitions must be >= 0 (0 = auto)")

    # -- resolution -----------------------------------------------------------
    @property
    def interpret_effective(self) -> bool:
        """Resolve ``interpret=None`` to the platform default.

        Pallas kernels must run interpreted on CPU (CI), but interpreting
        on a real TPU would silently deoptimize device runs — so auto
        means "interpret unless jax is actually backed by a TPU".
        """
        from ..kernels.ops import resolve_interpret

        return resolve_interpret(self.interpret)

    @property
    def backend_name(self) -> str:
        """The Session backend registry name this target places onto."""
        return self.kind

    def mesh(self):
        """Build the device mesh for a distributed target."""
        if self.kind != "distributed":
            raise ValueError(f"Target kind {self.kind!r} has no device mesh")
        import jax

        n = self.n_devices or jax.device_count()
        return dist_mesh(n, self.axis)

    def auto_partitions(self, n_vertices: int) -> int:
        """Resolve the dst-range partition count for a vertex count."""
        if self.n_partitions:
            return self.n_partitions
        return max(1, n_vertices // self.partition_vertices)

    # -- construction helpers -------------------------------------------------
    @staticmethod
    def from_options(options, kind: str = "local", **overrides) -> "Target":
        """Map a (possibly legacy) CompileOptions onto a Target.

        This is the compat shim's other half: ``CompileOptions(burst=False)``
        records ``("burst", False)`` in ``target_overrides``, and this
        constructor replays those overrides (plus any explicit kwargs) onto
        the Target defaults. Plain objects exposing the legacy attribute
        names (old pickles, duck types) are also accepted.
        """
        vals = {"kind": kind}
        stored = getattr(options, "target_overrides", None)
        if stored is not None:
            for name, value in stored:
                vals[name] = value
        elif options is not None:  # pre-split options object: read attributes
            for name in LEGACY_OPTION_FIELDS:
                if hasattr(options, name):
                    vals[name] = getattr(options, name)
        vals.update(overrides)
        return Target(**vals)

    @staticmethod
    def baseline() -> "Target":
        """Unoptimized reference substrate: random scatter, no
        partitioning/caching (the paper's handcrafted-HLS baseline)."""
        return Target(
            burst=False, cache=False, shuffle=False, compact_frontier=False,
            pallas=False,
        )

    @staticmethod
    def with_only(opt: str) -> "Target":
        """Fig. 9 ablation points: exactly one memory optimization enabled."""
        if opt not in ("burst", "cache", "shuffle"):
            raise ValueError(f"unknown ablation axis {opt!r}")
        return replace(Target.baseline(), **{opt: True})

    # -- serialization --------------------------------------------------------
    def to_dict(self) -> dict:
        return {f.name: getattr(self, f.name) for f in fields(self)}

    @staticmethod
    def from_dict(d: dict) -> "Target":
        known = {f.name for f in fields(Target)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(f"unknown Target fields in artifact: {unknown}")
        return Target(**d)

    def describe(self) -> str:
        mesh = f" x{self.n_devices or 'all'}({self.axis})" if self.kind == "distributed" else ""
        opts = ",".join(
            name for name in ("burst", "cache", "shuffle", "compact_frontier", "pallas")
            if getattr(self, name)
        ) or "none"
        return f"{self.kind}{mesh} [{opts}] parts={self.n_partitions or 'auto'}"


def dist_mesh(n_devices: int, axis: str):
    """The 1-D device mesh distributed targets run on.

    Its axis is ``Auto``: the engine combines a superstep's sharded output
    with single-device state in plain jnp ops, which ``Explicit`` axes
    (``jax.make_mesh``'s default) reject with a sharding type error.
    """
    import jax

    return jax.make_mesh(
        (n_devices,), (axis,), axis_types=(jax.sharding.AxisType.Auto,)
    )


#: Default Target: the single source of truth for substrate defaults — the
#: CompileOptions compat properties resolve unset legacy fields against it.
DEFAULT_TARGET = Target()
