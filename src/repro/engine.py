"""InferenceEngine — the declarative public API facade (DESIGN.md §7).

One object replaces the hand-wired ``plan_asymmetric(freqs=, dedup=,
cache=)`` → ``pack_plan`` → ``autotune`` → ``PartitionedEmbeddingBag`` /
``Server(drift=, cache=)`` kwarg chain::

    from repro.engine import EngineConfig, InferenceEngine

    config = EngineConfig(planner="asymmetric", distribution="zipf:1.2",
                          access="full", tuning="sweep")
    engine = InferenceEngine.build(table_data, workload, config)
    pooled = engine.lookup(indices)            # (N, B, E)
    server = engine.serve()                    # request-level serving
    handle = server.submit_request(query)      # Future-style handle
    server.pump(); pooled_one = handle.result()
    print(engine.plan_report())

``EngineConfig`` is a flat declarative dataclass — every field is a JSON
scalar or a plain dict, so a served deployment round-trips to/from one JSON
artifact (:meth:`EngineConfig.save` / :meth:`EngineConfig.load`) and is
reproducible from it bit-for-bit.

Stage behavior is pluggable through four small ``Protocol``s, each with a
named registry so third-party policies drop in without touching the engine:

* :class:`PlacementPolicy`   — workload → :class:`~repro.core.strategies.Plan`
  (builtin names wrap ``plan_baseline``/``plan_symmetric``/``plan_asymmetric``);
* :class:`AccessReductionPolicy` — which dedup/cache kwargs the planner is
  armed with (builtin: ``none``/``dedup``/``cache``/``full``);
* :class:`TuningPolicy`      — block-size selection at pack time (builtin:
  ``none``/``fixed``/``sweep`` = the :mod:`repro.core.autotune` sweep);
* :class:`DriftPolicy`       — online-replanning wiring for the server
  (builtin: ``none``/``replan`` = sketch → trigger → shadow re-pack →
  parity-checked hot swap via :class:`repro.serving.server.DriftConfig`).

The engine deliberately *delegates* to the existing layers —
``PartitionedEmbeddingBag`` for plan+pack+apply, ``Server`` for batching —
so an engine-built lookup is bit-identical to the manual chain; the facade
adds composition and a stable surface, not a second code path.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Callable, Mapping, Protocol, Sequence, runtime_checkable

import numpy as np

__all__ = [
    "ACCESS_POLICIES",
    "AccessReductionPolicy",
    "DRIFT_POLICIES",
    "DriftPolicy",
    "EngineConfig",
    "HARDWARE_PRESETS",
    "INTEGRITY_POLICIES",
    "InferenceEngine",
    "IntegrityPolicy",
    "PLACEMENT_POLICIES",
    "PlacementPolicy",
    "PolicyRegistry",
    "TUNING_POLICIES",
    "TuningPolicy",
    "VALIDATION_POLICIES",
    "ValidationPolicy",
]


# --------------------------------------------------------------------------
# Policy protocols + registries
# --------------------------------------------------------------------------


@runtime_checkable
class PlacementPolicy(Protocol):
    """Maps a workload onto cores.  Same signature as the planner functions
    in :mod:`repro.core.planner`, so any of them (or a third-party callable
    with the same shape) is a valid policy body."""

    def plan(self, workload, n_cores: int, model, **options):  # -> Plan
        ...


@runtime_checkable
class AccessReductionPolicy(Protocol):
    """Chooses the planner's access-reduction arming (DESIGN.md §6): the
    kwargs merged into the placement call (``dedup=``/``cache=``/sizing)."""

    def planner_kwargs(self, **options) -> dict:
        ...


@runtime_checkable
class TuningPolicy(Protocol):
    """Chooses the fused kernel's block sizes at pack time: the kwargs
    merged into :meth:`PartitionedEmbeddingBag.pack` (``autotune=`` /
    ``block_r=`` / ``block_b=``)."""

    def pack_kwargs(self, **options) -> dict:
        ...


@runtime_checkable
class ValidationPolicy(Protocol):
    """Builds the server's query-index validator (DESIGN.md §9): a callable
    ``payloads -> (payloads', counts, bad)`` run at batch release, or
    ``None`` for no validation.  ``rows`` are the workload's per-table
    vocabulary sizes."""

    def validator(self, *, rows, **options):
        ...


@runtime_checkable
class IntegrityPolicy(Protocol):
    """Wires packed-buffer corruption detection: ``manifest`` freezes the
    pack-time checksums (``None`` disables), ``server_config`` returns the
    cadence/guard knobs the server runs them under."""

    def manifest(self, packed, plan, **options):
        ...

    def server_config(self, **options):
        ...


@runtime_checkable
class DriftPolicy(Protocol):
    """Wires online replanning into the server: returns a
    :class:`repro.serving.server.DriftConfig` (or ``None`` for static
    serving).  ``baseline``/``extract_indices``/``replan`` are supplied by
    the engine; ``options`` come from ``EngineConfig.drift_options``."""

    def drift_config(self, *, baseline, extract_indices, replan, **options):
        ...


class PolicyRegistry:
    """Named factory registry for one policy kind.  ``register`` accepts a
    zero-arg factory (class or callable) and doubles as a decorator; unknown
    names raise with the registered alternatives listed."""

    def __init__(self, kind: str):
        self.kind = kind
        self._factories: dict[str, Callable[[], Any]] = {}

    def register(self, name: str, factory: Callable[[], Any] | None = None):
        if factory is None:  # decorator form
            return lambda f: self.register(name, f)
        if not isinstance(name, str) or not name:
            raise ValueError(f"{self.kind} policy name must be a non-empty string")
        self._factories[name] = factory
        return factory

    def create(self, name: str):
        if name not in self._factories:
            raise ValueError(
                f"unknown {self.kind} policy {name!r}; "
                f"registered: {self.names()}"
            )
        return self._factories[name]()

    def names(self) -> list[str]:
        return sorted(self._factories)


PLACEMENT_POLICIES = PolicyRegistry("placement")
ACCESS_POLICIES = PolicyRegistry("access-reduction")
TUNING_POLICIES = PolicyRegistry("tuning")
DRIFT_POLICIES = PolicyRegistry("drift")
VALIDATION_POLICIES = PolicyRegistry("validation")
INTEGRITY_POLICIES = PolicyRegistry("integrity")


class _PlannerPlacement:
    """Builtin placement: delegate to a :data:`repro.core.planner.PLANNERS`
    entry — the engine path and the manual chain share the planner code."""

    def __init__(self, planner_name: str):
        self.planner_name = planner_name

    def plan(self, workload, n_cores, model, **options):
        from repro.core.planner import PLANNERS

        return PLANNERS[self.planner_name](workload, n_cores, model, **options)


for _name in ("baseline", "symmetric", "asymmetric", "hierarchical"):
    PLACEMENT_POLICIES.register(
        _name, (lambda n: lambda: _PlannerPlacement(n))(_name)
    )


class _AccessArming:
    def __init__(self, dedup: bool, cache: bool):
        self.dedup, self.cache = dedup, cache

    def planner_kwargs(self, **options) -> dict:
        if not (self.dedup or self.cache):
            return {}
        return {"dedup": self.dedup, "cache": self.cache, **options}


ACCESS_POLICIES.register("none", lambda: _AccessArming(False, False))
ACCESS_POLICIES.register("dedup", lambda: _AccessArming(True, False))
ACCESS_POLICIES.register("cache", lambda: _AccessArming(False, True))
ACCESS_POLICIES.register("full", lambda: _AccessArming(True, True))


class _NoTuning:
    def pack_kwargs(self, **options) -> dict:
        return {}


class _FixedTuning:
    """Caller-pinned block sizes: ``tuning_options`` pass straight through
    (``block_r``/``block_b``)."""

    def pack_kwargs(self, **options) -> dict:
        return {k: options[k] for k in ("block_r", "block_b") if k in options}


class _SweepTuning:
    """The :func:`repro.core.autotune.autotune_block_sizes` compiled sweep,
    recorded in ``plan.meta["tuning"]`` by ``bag.pack(autotune=True)``."""

    def pack_kwargs(self, **options) -> dict:
        return {"autotune": True}


TUNING_POLICIES.register("none", _NoTuning)
TUNING_POLICIES.register("fixed", _FixedTuning)
TUNING_POLICIES.register("sweep", _SweepTuning)


class _NoDrift:
    def drift_config(self, *, baseline, extract_indices, replan, **options):
        return None


class _ReplanDrift:
    """The PR3 drift state machine: sketch → hysteresis trigger → shadow
    re-pack → parity-gated hot swap.  ``options`` are DriftConfig knobs
    (threshold/check_every/patience/cooldown/metric/...)."""

    def drift_config(self, *, baseline, extract_indices, replan, **options):
        from repro.serving.server import DriftConfig

        return DriftConfig(
            baseline=baseline,
            extract_indices=extract_indices,
            replan=replan,
            **options,
        )


DRIFT_POLICIES.register("none", _NoDrift)
DRIFT_POLICIES.register("replan", _ReplanDrift)


class _IndexValidation:
    """Builtin validation policies: the three OOV/negative-index modes of
    :class:`repro.serving.validation.IndexValidator` (``clip`` is today's
    pass-through behavior — bit-identical outputs, counters only)."""

    def __init__(self, mode: str):
        self.mode = mode

    def validator(self, *, rows, **options):
        from repro.serving.validation import payload_validator

        return payload_validator(rows, self.mode)


for _mode in ("clip", "null-row", "reject"):
    VALIDATION_POLICIES.register(
        _mode, (lambda m: lambda: _IndexValidation(m))(_mode)
    )


class _NoIntegrity:
    def manifest(self, packed, plan, **options):
        return None

    def server_config(self, **options):
        return None


class _ChecksumIntegrity:
    """Builtin ``checksum`` policy: per-region CRC32 manifest at pack time
    (:class:`repro.core.integrity.IntegrityManifest`), verified on a batch
    cadence + on drift hot-swaps, with NaN/Inf output guards.  Options:
    ``check_every`` (batches between sweeps, default 64; 0 = only on
    hot-swap/poisoned-output) and ``nan_guard`` (default True)."""

    def manifest(self, packed, plan, **options):
        from repro.core.integrity import IntegrityManifest

        return IntegrityManifest.from_packed(packed, plan)

    def server_config(self, **options):
        return {
            "check_every": int(options.get("check_every", 64)),
            "nan_guard": bool(options.get("nan_guard", True)),
        }


INTEGRITY_POLICIES.register("none", _NoIntegrity)
INTEGRITY_POLICIES.register("checksum", _ChecksumIntegrity)


# --------------------------------------------------------------------------
# EngineConfig
# --------------------------------------------------------------------------


HARDWARE_PRESETS = ("tpu_v5e", "a100", "ascend_910")


def _hardware_presets() -> dict:
    from repro.core import cost_model

    # single source: each preset name is its cost_model constant, lowercased
    return {name: getattr(cost_model, name.upper()) for name in HARDWARE_PRESETS}


@dataclasses.dataclass
class EngineConfig:
    """Declarative build recipe for :class:`InferenceEngine`.

    Every field is JSON-representable (scalars + plain dicts), so a config
    round-trips through :meth:`to_json`/:meth:`from_json` and a deployment
    is reproducible from the one artifact.  Policy fields name registry
    entries; their ``*_options`` dicts are passed to the policy verbatim.

    ``distribution`` is a CLI-style spec string (``"uniform"``,
    ``"zipf:1.2"``, ``"hotset:0.01:0.9"``, a workload preset name, …) —
    the access histograms the plan is priced under; ``None`` keeps the
    paper's uniform assumption.  A drift-schedule spec uses its phase-0
    distribution for the initial plan.
    """

    # scenario model (DESIGN.md §10): "pooled" = the raw embedding lookup;
    # a repro.models.registry.SCENARIOS name serves that wrapper's tower on
    # top of the engine's fused lookups (make_step/split come from the
    # wrapper).  model_options are factory kwargs (batch=/seed=).
    model: str = "pooled"
    model_options: dict = dataclasses.field(default_factory=dict)
    # placement
    planner: str = "asymmetric"
    planner_options: dict = dataclasses.field(default_factory=dict)
    distribution: str | None = None
    # access reduction (DESIGN.md §6)
    access: str = "none"
    access_options: dict = dataclasses.field(default_factory=dict)
    # block-size tuning (DESIGN.md §4)
    tuning: str = "none"
    tuning_options: dict = dataclasses.field(default_factory=dict)
    # online replanning (DESIGN.md §5)
    drift: str = "none"
    drift_options: dict = dataclasses.field(default_factory=dict)
    # data-plane integrity (DESIGN.md §9): input validation + buffer
    # corruption detection.  validation="clip" is today's behavior made
    # explicit (pass-through + counters, bit-identical outputs).
    validation: str = "clip"
    validation_options: dict = dataclasses.field(default_factory=dict)
    integrity: str = "none"
    integrity_options: dict = dataclasses.field(default_factory=dict)
    # executor
    layout: str = "ragged"
    use_kernels: str = "fused"  # "fused" | "xla"
    reduce_mode: str = "sparse"  # "sparse" | "psum" | "ring"
    # dedup'd gather implementation (DESIGN.md §11): "auto" = the planner's
    # per-chunk cost-modeled crossover choice, "onehot"/"sparse" force one
    # path everywhere.  "sparse" rides the dedup machinery, so it requires
    # an access policy that arms dedup.
    kernel_path: str = "auto"
    # hardware / cost model
    hardware: str = "tpu_v5e"
    hardware_options: dict = dataclasses.field(default_factory=dict)
    dtype: str = "float32"
    n_cores: int | None = None  # deprecated: use mesh_shape (None = devices)
    # two-level mesh (DESIGN.md §12): (hosts, cores_per_host).  None falls
    # back to n_cores as (1, n_cores) — the flat single-host mesh — with a
    # DeprecationWarning when n_cores was set explicitly.  The planner sees
    # hosts * cores_per_host cores; the "hierarchical" planner additionally
    # keeps each un-sharded table's cores on one host.
    mesh_shape: tuple | list | None = None
    # simulate=True skips the plan-cores == device-mesh check at build time
    # so plan/model-only work (benches, reports) can study a 4x8 mesh on one
    # CPU device.  Execution entry points still raise MeshShapeError.
    simulate: bool = False
    # serving (DESIGN.md §8): batching + admission control + deadlines +
    # degraded-mode fault containment
    max_batch: int = 256
    max_wait_s: float = 0.0
    max_queue: int | None = None  # None = unbounded admission queue
    admission: str = "block"  # "block" | "reject" | "shed-oldest"
    deadline_s: float | None = None  # default per-request deadline
    adaptive_batching: bool = False  # arrival-rate-aware early release
    degrade_after: int = 3  # consecutive batch failures before degraded
    #   mode (0 disables the fallback path entirely)
    probe_every: int = 4  # degraded-mode primary-probe cadence

    def __post_init__(self) -> None:
        # JSON round-trips deliver mesh_shape as a list; normalize so a
        # loaded config compares equal to the one that was saved
        if self.mesh_shape is not None:
            self.mesh_shape = tuple(self.mesh_shape)

    def validate(self) -> None:
        if self.layout not in ("ragged", "dense"):
            raise ValueError(f"unknown layout {self.layout!r}")
        if self.use_kernels not in ("fused", "xla"):
            raise ValueError(
                f"use_kernels must be 'fused' or 'xla', got {self.use_kernels!r}"
            )
        if self.reduce_mode not in ("sparse", "psum", "ring"):
            raise ValueError(f"unknown reduce_mode {self.reduce_mode!r}")
        if self.hardware not in _hardware_presets():
            raise ValueError(
                f"unknown hardware preset {self.hardware!r}; "
                f"known: {sorted(_hardware_presets())}"
            )
        if self.dtype not in ("float32", "bfloat16", "float16"):
            raise ValueError(f"unknown dtype {self.dtype!r}")
        if self.max_batch <= 0:
            raise ValueError(f"max_batch must be positive, got {self.max_batch}")
        if self.max_wait_s < 0:
            raise ValueError(
                f"max_wait_s must be >= 0, got {self.max_wait_s} "
                "(0 releases as soon as anything is queued)"
            )
        from repro.serving.server import ADMISSION_POLICIES

        if self.admission not in ADMISSION_POLICIES:
            raise ValueError(
                f"unknown admission policy {self.admission!r}; "
                f"known: {list(ADMISSION_POLICIES)}"
            )
        if self.max_queue is not None and self.max_queue <= 0:
            raise ValueError(
                f"max_queue must be positive (or None for unbounded), "
                f"got {self.max_queue}"
            )
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be positive (or None), got {self.deadline_s}"
            )
        if self.degrade_after < 0:
            raise ValueError(
                f"degrade_after must be >= 0 (0 disables degraded mode), "
                f"got {self.degrade_after}"
            )
        if self.probe_every <= 0:
            raise ValueError(
                f"probe_every must be positive, got {self.probe_every}"
            )
        if self.kernel_path not in ("auto", "onehot", "sparse"):
            raise ValueError(
                f"kernel_path must be 'auto', 'onehot' or 'sparse', "
                f"got {self.kernel_path!r}"
            )
        if self.kernel_path == "sparse":
            # the sparse gather rides the dedup uniq/cnt machinery, which
            # only exists in the fused ragged asymmetric executor with a
            # dedup-arming access policy.
            if self.access not in ("dedup", "full"):
                raise ValueError(
                    "kernel_path='sparse' requires access='dedup' or 'full' "
                    "(the sparse gather rides the dedup machinery)"
                )
        if self.mesh_shape is not None:
            from repro.core.mesh import resolve_mesh_shape

            # raises MeshShapeError on bad geometry / n_cores disagreement
            resolve_mesh_shape(self.mesh_shape, self.n_cores, warn=False)
        if self.access != "none":
            # same constraints the serve CLI enforced: the access-reduction
            # subsystem lives in the fused ragged executor and its knobs are
            # planner kwargs only plan_asymmetric (and the hierarchical
            # planner, which delegates to it per host) accepts.
            if self.planner not in ("asymmetric", "hierarchical"):
                raise ValueError(
                    "access reduction requires planner='asymmetric' or "
                    "'hierarchical'"
                )
            if self.layout != "ragged":
                raise ValueError("access reduction requires layout='ragged'")
            if self.use_kernels != "fused":
                raise ValueError("access reduction requires use_kernels='fused'")
        if self.model != "pooled":
            from repro.models.registry import SCENARIOS

            if self.model not in SCENARIOS:
                raise ValueError(
                    f"unknown scenario model {self.model!r}; registered: "
                    f"{sorted(SCENARIOS)} (or 'pooled')"
                )
        if self.integrity != "none":
            check_every = self.integrity_options.get("check_every", 64)
            if not isinstance(check_every, int) or check_every < 0:
                raise ValueError(
                    f"integrity_options['check_every'] must be an int >= 0, "
                    f"got {check_every!r}"
                )
        # fail early on unknown policy names (before any planning work)
        for reg, name in (
            (PLACEMENT_POLICIES, self.planner),
            (ACCESS_POLICIES, self.access),
            (TUNING_POLICIES, self.tuning),
            (DRIFT_POLICIES, self.drift),
            (VALIDATION_POLICIES, self.validation),
            (INTEGRITY_POLICIES, self.integrity),
        ):
            reg.create(name)

    # -- JSON round-trip ----------------------------------------------------

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "EngineConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(f"unknown EngineConfig fields: {unknown}")
        return cls(**dict(d))

    def to_json(self, **dumps_kwargs) -> str:
        dumps_kwargs.setdefault("indent", 2)
        return json.dumps(self.to_dict(), **dumps_kwargs)

    @classmethod
    def from_json(cls, s: str) -> "EngineConfig":
        return cls.from_dict(json.loads(s))

    def save(self, path) -> None:
        Path(path).write_text(self.to_json() + "\n")

    @classmethod
    def load(cls, path) -> "EngineConfig":
        return cls.from_json(Path(path).read_text())


# --------------------------------------------------------------------------
# InferenceEngine
# --------------------------------------------------------------------------


def _payload_indices(q) -> np.ndarray:
    """A query payload is either the raw (N, s) index array or a dict with
    an ``"indices"`` entry (the serving convention)."""
    return np.asarray(q["indices"] if isinstance(q, Mapping) else q)


def _place_on_mesh(packed, mesh):
    """Packed arrays placed one core per device when the mesh's ``model``
    axis matches the plan; a ``simulate=True`` build keeps them as built."""
    if packed.n_cores != dict(mesh.shape).get("model", 1):
        return packed
    from repro.core.partition import place_packed

    return place_packed(packed, mesh)


class InferenceEngine:
    """The facade: plan → access-reduction arming → pack → (optional)
    autotune, built once by :meth:`build`, exposing ``lookup`` / ``serve``
    / ``stats`` / ``plan_report``.

    Attributes useful for composition (e.g. a DLRM forward on top of the
    packed embeddings): ``bag`` (the :class:`PartitionedEmbeddingBag`),
    ``packed`` (the :class:`PackedPlan`), ``plan``, ``mesh``, ``freqs``
    (the histograms the plan was priced under), ``cost_model``.
    """

    def __init__(
        self,
        *,
        config: EngineConfig,
        workload,
        bag,
        packed,
        mesh,
        freqs,
        table_data,
        cost_model,
        manifest=None,
        scenario=None,
        tuning_cache=None,
    ):
        self.config = config
        self.workload = workload
        self.bag = bag
        self.packed = packed
        self.mesh = mesh
        self.freqs = freqs
        self.cost_model = cost_model
        self.manifest = manifest  # pack-time integrity checksums (or None)
        self.scenario = scenario  # ScenarioModel wrapper (or None = pooled)
        self.tuning_cache = tuning_cache  # sweep memo shared across rebuilds
        self._table_data = table_data
        self._server = None

    # -- construction -------------------------------------------------------

    @classmethod
    def build(
        cls,
        tables,
        workload,
        config: EngineConfig | None = None,
        *,
        mesh=None,
        freqs=None,
        rng=None,
        tuning_cache=None,
    ) -> "InferenceEngine":
        """Build the full pipeline from a declarative config.

        ``tables`` — per-table (m_i, E) embedding arrays, or ``None`` to
        initialize fresh parameters (``rng`` seeds them; default key 0), or
        the string ``"abstract"`` for shape-only packing (dry runs).
        ``freqs`` overrides ``config.distribution`` with explicit per-table
        :class:`~repro.data.distributions.RowProbs` (how the drift engine
        rebuilds from *measured* histograms).  ``tuning_cache`` (a
        :class:`repro.core.autotune.TuningCache`; default: a fresh one)
        memoizes autotune sweeps — :meth:`rebuild` passes the engine's own
        cache so a shape-identical drift replan reuses prior picks.
        """
        import dataclasses as _dc

        import jax

        from repro import compat
        from repro.core.cost_model import analytic_model
        from repro.core.embedding import PartitionedEmbeddingBag

        from repro.core.mesh import MeshShapeError, resolve_mesh_shape

        config = config if config is not None else EngineConfig()
        config.validate()

        hosts, cores_per_host = resolve_mesh_shape(
            config.mesh_shape, config.n_cores,
            default_cores=jax.device_count(),
        )
        n_cores = hosts * cores_per_host
        hw = _hardware_presets()[config.hardware]
        if config.hardware_options:
            hw = _dc.replace(hw, **config.hardware_options)
        model = analytic_model(hw)

        if freqs is None and config.distribution:
            from repro.data.distributions import (
                DriftSchedule,
                get_distribution,
                workload_probs,
            )

            dist = get_distribution(config.distribution)
            if isinstance(dist, DriftSchedule):
                dist = dist.at(0)
            freqs = workload_probs(workload, dist)

        placement = PLACEMENT_POLICIES.create(config.planner)
        access = ACCESS_POLICIES.create(config.access)
        tuning = TUNING_POLICIES.create(config.tuning)

        planner_kwargs = dict(config.planner_options)
        planner_kwargs.update(access.planner_kwargs(**config.access_options))
        if freqs is not None:
            planner_kwargs["freqs"] = freqs
        if config.planner in ("asymmetric", "hierarchical"):
            # the per-chunk dense-vs-sparse crossover choice is priced by
            # the planner and recorded in plan.meta["kernel"]; pack reads
            # it back when no explicit kernel_path is given.
            planner_kwargs.setdefault("kernel_path", config.kernel_path)
        if config.planner == "hierarchical":
            planner_kwargs.setdefault("hosts", hosts)

        import jax.numpy as jnp

        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                 "float16": jnp.float16}[config.dtype]
        bag = PartitionedEmbeddingBag(
            workload,
            n_cores=n_cores,
            planner=placement.plan,
            cost_model=model,
            planner_kwargs=planner_kwargs,
            layout=config.layout,
            dtype=dtype,
        )
        if isinstance(tables, str):
            if tables != "abstract":
                raise ValueError(f"unknown tables spec {tables!r}")
            table_data = None
        elif tables is None:
            table_data = bag.init(rng if rng is not None else jax.random.PRNGKey(0))
        else:
            table_data = list(tables)
        if tuning_cache is None:
            from repro.core.autotune import TuningCache

            tuning_cache = TuningCache()
        packed = bag.pack(
            table_data,
            tuning_cache=tuning_cache,
            **tuning.pack_kwargs(**config.tuning_options),
        )

        integrity = INTEGRITY_POLICIES.create(config.integrity)
        manifest = integrity.manifest(
            packed, bag.plan, **config.integrity_options
        )

        if mesh is None:
            mesh = compat.make_mesh((1, jax.device_count()), ("data", "model"))
        axis_size = dict(mesh.shape).get("model", 1)
        if n_cores != axis_size and not config.simulate:
            raise MeshShapeError(
                f"plan spans {n_cores} cores (mesh_shape {hosts}x"
                f"{cores_per_host}) but the device mesh 'model' axis has "
                f"{axis_size} device(s) (jax.device_count()="
                f"{jax.device_count()}); either run under a matching device "
                f"mesh (e.g. XLA_FLAGS=--xla_force_host_platform_device_"
                f"count={n_cores}), set mesh_shape=(1, {axis_size}), or pass "
                "simulate=True for plan/model-only work (execution will "
                "still raise)"
            )
        return cls(
            config=config,
            workload=workload,
            bag=bag,
            packed=_place_on_mesh(packed, mesh),
            mesh=mesh,
            freqs=freqs,
            table_data=table_data,
            cost_model=model,
            manifest=manifest,
            tuning_cache=tuning_cache,
        )

    @classmethod
    def from_scenario(
        cls,
        scenario,
        config: EngineConfig | None = None,
        *,
        mesh=None,
        freqs=None,
    ) -> "InferenceEngine":
        """Build an engine over a :class:`~repro.models.scenarios.
        ScenarioModel`: the wrapper's workload + extracted tables go through
        the normal :meth:`build` pipeline, and the returned engine carries
        the wrapper so :meth:`serve` runs its tower step (and drift
        hot-swaps rebuild it) without extra wiring."""
        import dataclasses as _dc

        config = config if config is not None else EngineConfig()
        name = getattr(scenario, "name", None)
        if config.model == "pooled" and name is not None:
            from repro.models.registry import SCENARIOS

            if name in SCENARIOS:  # stamp the recipe into the artifact
                config = _dc.replace(config, model=name)
        engine = cls.build(
            scenario.table_data(), scenario.workload, config,
            mesh=mesh, freqs=freqs,
        )
        engine.scenario = scenario
        return engine

    @classmethod
    def build_scenario(
        cls,
        name: str | None = None,
        config: EngineConfig | None = None,
        *,
        mesh=None,
        freqs=None,
        **factory_kwargs,
    ) -> "InferenceEngine":
        """Resolve a registered scenario by name (default: ``config.model``)
        and build it — the one-call path from a JSON config artifact with a
        ``model`` field to a served scenario.  ``factory_kwargs`` override
        ``config.model_options`` (``batch=``/``seed=``)."""
        from repro.models.registry import get_scenario

        config = config if config is not None else EngineConfig()
        name = name or (config.model if config.model != "pooled" else None)
        if name is None:
            raise ValueError(
                "build_scenario needs a scenario name (argument or "
                "config.model)"
            )
        opts = {**config.model_options, **factory_kwargs}
        scenario = get_scenario(name, **opts)
        return cls.from_scenario(scenario, config, mesh=mesh, freqs=freqs)

    def reference_view(self) -> "InferenceEngine":
        """A shallow engine view over the SAME bag/packed tables whose
        executor knobs are forced to the XLA reference path
        (``use_kernels="xla"``): the degraded-mode fallback the server
        serves from when the fused path keeps crashing (DESIGN.md §8).
        The reference path is parity-identical on any packed plan
        (including dedup/cache-armed ones), so falling back never changes
        results — only speed."""
        import dataclasses as _dc

        view = InferenceEngine(
            config=_dc.replace(self.config, use_kernels="xla"),
            workload=self.workload,
            bag=self.bag,
            packed=self.packed,
            mesh=self.mesh,
            freqs=self.freqs,
            table_data=self._table_data,
            cost_model=self.cost_model,
            manifest=self.manifest,
            scenario=self.scenario,
            tuning_cache=self.tuning_cache,
        )
        return view

    def rebuild(self, freqs) -> "InferenceEngine":
        """Same config + tables, re-planned/re-packed under new histograms —
        the shadow re-pack the drift policy runs off the hot path.  The
        scenario wrapper (tower params + step maker) carries over so a
        hot-swap re-invokes the same model's ``make_step``, and the tuning
        cache carries over so a shape-identical re-plan skips the autotune
        sweep (hits surface in ``stats()["tuning"]["cache"]``)."""
        engine = InferenceEngine.build(
            self._table_data if self._table_data is not None else "abstract",
            self.workload,
            self.config,
            mesh=self.mesh,
            freqs=freqs,
            tuning_cache=self.tuning_cache,
        )
        engine.scenario = self.scenario
        return engine

    # -- data-plane integrity (DESIGN.md §9) --------------------------------

    def verify_integrity(self) -> list[tuple]:
        """Re-checksum the packed buffers against the pack-time manifest;
        returns the corrupt region keys (empty = clean, or no manifest)."""
        if self.manifest is None:
            return []
        return self.manifest.verify(self.packed)

    def heal(self) -> dict:
        """Targeted repair of corrupt buffer regions: re-materialize them
        from the source tables (bit-exact) or zero-quarantine regions with
        no source, replacing ``self.packed``.  The repo's steps pass
        ``engine.packed`` to their jitted forward on every call; a step that
        closes over the packed arrays must be rebuilt after a heal
        (``serve``'s integrity wiring does this and swaps it in
        atomically)."""
        if self.manifest is None:
            return {"healed": [], "quarantined": [], "clean": True}
        new_packed, report = self.manifest.repair(
            self.packed, self.plan, self.workload.tables, self._table_data
        )
        self.packed = _place_on_mesh(new_packed, self.mesh)
        return report

    # -- execution ----------------------------------------------------------

    @property
    def plan(self):
        return self.bag.plan

    @property
    def table_data(self):
        return self._table_data

    @property
    def _use_kernels(self):
        return "fused" if self.config.use_kernels == "fused" else False

    def _require_executable(self) -> None:
        """Raise when the plan spans more cores than the device mesh holds.

        ``simulate=True`` builds are plan/model-only artifacts: shard_map
        over an undersized mesh would silently hand each device the *full*
        stacked buffers and drop every core's partial but core 0's — the
        exact silent-fallback bug this check closes (DESIGN.md §12)."""
        from repro.core.mesh import MeshShapeError

        axis_size = dict(self.mesh.shape).get("model", 1)
        if self.packed.n_cores != axis_size:
            raise MeshShapeError(
                f"cannot execute: plan spans {self.packed.n_cores} cores but "
                f"the device mesh 'model' axis has {axis_size} device(s) — "
                "this engine was built with simulate=True for plan/model "
                "work; to run lookups, rebuild under a matching device mesh "
                "(e.g. XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{self.packed.n_cores})"
            )

    def lookup(self, indices) -> Any:
        """Partitioned pooled lookup: per-table index arrays (or the stacked
        (N, B, s_max) tensor with ``-1`` padding) → (N, B, E).  Exactly
        ``bag.apply`` under the config's executor knobs — jit-able."""
        return self._apply(self.packed, indices)

    def _apply(self, packed, indices):
        self._require_executable()
        return self.bag.apply(
            packed,
            indices,
            mesh=self.mesh,
            use_kernels=self._use_kernels,
            reduce_mode=self.config.reduce_mode,
        )

    def jitted_lookup(self):
        """:meth:`lookup` jitted with the packed tables as an argument:
        ``fn(packed, indices) -> (N, B, E)``.  A jitted closure over
        ``self.packed`` would bake every table into the executable."""
        import jax

        return jax.jit(self._apply)

    def _default_step(self):
        """payloads (list of queries) → (N, B, E) numpy, jitted once."""
        import jax

        apply = self.jitted_lookup()

        def step(payloads):
            idx = np.stack([_payload_indices(q) for q in payloads], axis=1)
            return np.asarray(jax.block_until_ready(apply(self.packed, idx)))

        step.bag = self.bag
        return step

    @staticmethod
    def _default_split(out, n: int):
        """(N, B, E) batch output → per-query (N, E) slices."""
        return [out[:, i] for i in range(n)]

    def serve(
        self,
        *,
        make_step: Callable[["InferenceEngine"], Callable] | None = None,
        split_fn: Callable[[Any, int], Sequence[Any]] | None = None,
        max_batch: int | None = None,
        max_wait_s: float | None = None,
        fault_injector=None,
        **server_kwargs,
    ):
        """Build a :class:`repro.serving.server.Server` driven by this
        engine: microbatching behind ``submit_request(query) -> handle``,
        drift replanning per the config's drift policy.

        ``make_step(engine) -> step`` customizes what runs per batch (e.g.
        a full DLRM forward on ``engine.bag``/``engine.packed``); it is also
        how a drift hot-swap rebuilds — the policy calls ``make_step`` again
        on the re-planned engine.  Default: the pooled embedding lookup,
        with per-query results split as (N, E) slices.

        Robustness semantics come from the config: ``max_queue`` +
        ``admission`` bound the queue, ``deadline_s`` shed stale requests,
        and when ``degrade_after > 0`` and the primary executor is the
        fused kernel path, a *fallback step* built from ``make_step`` over
        :meth:`reference_view` (the XLA reference path on the same packed
        tables) serves batches in degraded mode after repeated failures.

        Data-plane integrity (DESIGN.md §9) is wired per the config's
        ``validation``/``integrity`` policies: the validator runs at batch
        release, and with an integrity manifest the step carries
        ``integrity_verify``/``integrity_repair`` hooks the server's
        checksum cadence + NaN guard act through — a repair re-materializes
        the corrupt regions and swaps a freshly built step in atomically.
        ``fault_injector`` threads a seeded
        :class:`repro.serving.faults.FaultInjector` through the server and
        the replan path (chaosbench / fault-containment tests).
        """
        from repro.serving.server import Server

        if make_step is None and self.scenario is not None:
            # per-model step wiring: the scenario's tower over the fused
            # lookups, re-invoked on every drift hot-swap / heal rebuild.
            make_step = self.scenario.make_step
            if split_fn is None:
                split_fn = self.scenario.split
        maker = make_step or (lambda eng: eng._default_step())

        def _make_fallback(eng):
            if self.config.degrade_after > 0 and self.config.use_kernels == "fused":
                # built eagerly but jitted lazily: the reference step
                # compiles only if a batch actually falls back to it.
                return maker(eng.reference_view())
            return None

        def _wire(step, eng):
            """Attach the engine-side hooks the server's integrity machinery
            (and a drift hot-swap's shadow) act through.  Hooks bind to the
            step's OWN engine so they stay correct across swaps."""
            if getattr(step, "bag", None) is None:
                step.bag = eng.bag
            step.rebuild = lambda: _wire(maker(eng), eng)
            if eng.manifest is not None:
                step.integrity_verify = eng.verify_integrity

                def _repair(bad):
                    report = eng.heal()
                    return {
                        "step_fn": _wire(maker(eng), eng),
                        "fallback_step_fn": _make_fallback(eng),
                        "report": report,
                    }

                step.integrity_repair = _repair
            return step

        step0 = _wire(maker(self), self)
        fallback = server_kwargs.pop("fallback_step_fn", None)
        if fallback is None:
            fallback = _make_fallback(self)

        def _replan(measured):
            if fault_injector is not None:
                fault_injector.fire("replan", batch=None)
            shadow_engine = self.rebuild(measured)
            return _wire(maker(shadow_engine), shadow_engine)

        baseline = self.freqs
        if baseline is None:
            # drift needs something to diff against: the uniform assumption
            # the plan was implicitly priced under.
            from repro.data.distributions import RowProbs

            baseline = [RowProbs.uniform(t.rows) for t in self.workload.tables]
        drift_policy = DRIFT_POLICIES.create(self.config.drift)
        drift_cfg = drift_policy.drift_config(
            baseline=baseline,
            extract_indices=lambda payloads: np.stack(
                [_payload_indices(q) for q in payloads], axis=1
            ),
            replan=_replan,
            **self.config.drift_options,
        )

        validation_policy = VALIDATION_POLICIES.create(self.config.validation)
        validator = validation_policy.validator(
            rows=[t.rows for t in self.workload.tables],
            **self.config.validation_options,
        )
        integrity_policy = INTEGRITY_POLICIES.create(self.config.integrity)
        integrity_cfg = integrity_policy.server_config(
            **self.config.integrity_options
        )

        kwargs = dict(
            max_batch=max_batch or self.config.max_batch,
            max_wait_s=(
                max_wait_s if max_wait_s is not None else self.config.max_wait_s
            ),
            layout=self.bag.layout_summary(),
            exec_mode={
                "use_kernels": self.config.use_kernels,
                "reduce_mode": self.config.reduce_mode,
            },
            cache=dict(self.plan.meta.get("cache") or {}),
            drift=drift_cfg,
            split_fn=split_fn or self._default_split,
            max_queue=self.config.max_queue,
            admission=self.config.admission,
            deadline_s=self.config.deadline_s,
            adaptive_batching=self.config.adaptive_batching,
            fallback_step_fn=fallback,
            degrade_after=self.config.degrade_after,
            probe_every=self.config.probe_every,
            validator=validator,
            integrity=integrity_cfg,
            fault_injector=fault_injector,
        )
        kwargs.update(server_kwargs)  # explicit kwargs override the config
        srv = Server(step0, **kwargs)
        self._server = srv
        return srv

    # -- introspection ------------------------------------------------------

    def stats(self) -> dict:
        """Plan/layout/tuning/cache summary (+ live server stats if
        :meth:`serve` was called)."""
        from repro.core.planner import predicted_p99

        plan = self.plan
        out = {
            "model": self.config.model,
            "workload": self.workload.name,
            "n_cores": plan.n_cores,
            "planner": plan.meta.get("planner"),
            "n_chunks": len(plan.assignments),
            "n_symmetric": len(plan.symmetric_tables),
            "lif": plan.meta.get("lif"),
            "predicted_p99_us": predicted_p99(
                self.cost_model, self.workload.tables, self.workload.batch,
                plan, self.freqs,
            ) * 1e6,
            "layout": self.bag.layout_summary(),
            "config": self.config.to_dict(),
        }
        for key in ("cache", "tuning", "distribution", "kernel", "mesh"):
            if plan.meta.get(key) is not None:
                out[key] = plan.meta[key]
        mesh_meta = plan.meta.get("mesh") or {}
        out["mesh_shape"] = [
            int(mesh_meta.get("hosts", 1)),
            int(mesh_meta.get("cores_per_host", plan.n_cores)),
        ]
        if out["mesh_shape"][0] > 1:
            from repro.core.traffic import modeled_cross_host_traffic

            xh = modeled_cross_host_traffic(
                plan, self.workload.tables, self.workload.batch, self.freqs
            )
            out["cross_host"] = {
                k: xh[k] for k in (
                    "cross_host_bytes", "flat_allgather_bytes",
                    "reduction_vs_flat", "bucket_entries", "unique_cap",
                )
            }
        if self._server is not None:
            out["server"] = self._server.stats()
        return out

    def _placement_tree(self, kern: dict) -> list[str]:
        """Placement as a host → core → chunk tree with per-level modeled
        bytes (DESIGN.md §12): each chunk line carries its modeled HBM
        lookup bytes, each core and host line the sum over its children,
        and on a multi-host mesh each host line adds the bytes its owner
        buckets put on the cross-host wire."""
        from repro.core.traffic import (
            modeled_cross_host_traffic,
            modeled_plan_traffic,
        )

        plan = self.plan
        tables = self.workload.tables
        batch = self.workload.batch
        traffic = modeled_plan_traffic(plan, tables, batch, self.freqs)
        chunk_bytes = traffic["per_chunk_bytes"]
        mesh_meta = plan.meta.get("mesh") or {}
        hosts = int(mesh_meta.get("hosts", 1))
        cph = int(mesh_meta.get("cores_per_host", plan.n_cores))
        xh = (
            modeled_cross_host_traffic(plan, tables, batch, self.freqs)
            if hosts > 1 else None
        )

        recs = list(zip(plan.assignments, kern["per_chunk"], chunk_bytes))
        lines: list[str] = []
        for h in range(hosts):
            host_recs = [r for r in recs if r[0].core // cph == h]
            host_bytes = sum(b for *_, b in host_recs)
            host_line = (
                f"  host {h}: {len(host_recs)} chunks, "
                f"modeled lookup {host_bytes:,}B"
            )
            if xh is not None:
                host_line += (
                    f", cross-host {xh['per_host_bytes'][h]:,.0f}B"
                )
            lines.append(host_line)
            for core in sorted({r[0].core for r in host_recs}):
                core_recs = [r for r in host_recs if r[0].core == core]
                core_bytes = sum(b for *_, b in core_recs)
                lines.append(
                    f"    core {core}: {len(core_recs)} chunks, "
                    f"modeled lookup {core_bytes:,}B"
                )
                for a, rec, b in core_recs:
                    strat = getattr(a.strategy, "name", str(a.strategy))
                    lines.append(
                        f"      chunk table={rec['table']} "
                        f"rows={rec['rows']} strategy={strat} "
                        f"kernel={rec['path']} "
                        f"(modeled onehot {rec['onehot_us']:.2f}us / "
                        f"sparse {rec['sparse_us']:.2f}us, lookup {b:,}B)"
                    )
        return lines

    def plan_report(self) -> str:
        """Human-readable build report (what ``launch/serve.py`` prints)."""
        s = self.stats()
        lines = [
            f"model {self.config.model}",
            f"workload {self.workload.summary()}",
            f"plan: {s['n_chunks']} chunks, {s['n_symmetric']} symmetric, "
            f"{s['n_cores']} cores, planner={s['planner']}, "
            f"predicted P99 {s['predicted_p99_us']:.1f}us",
        ]
        lay = s.get("layout") or {}
        if lay:
            lines.append(
                f"layout={lay['kind']} chunk_bytes={lay['chunk_bytes']:,} "
                f"(dense would be {lay['dense_bytes']:,}; "
                f"{lay['bytes_vs_dense']:.2%} of dense, "
                f"padding_frac={lay['padding_frac']:.2%})"
            )
        tuning = s.get("tuning")
        if tuning and tuning.get("best"):
            best = tuning["best"]
            lines.append(
                f"autotuned block_r={best['block_r']} "
                f"block_b={best['block_b'] or 'auto'} "
                f"({len(tuning['candidates'])} candidates, "
                f"backend={tuning['backend']})"
            )
        acc = s.get("cache")
        if acc:
            lines.append(
                f"access-reduction dedup={acc['dedup']} "
                f"unique_cap={acc['unique_cap']} cache_rows={acc['cache_rows']} "
                f"(modeled coverage={acc['coverage']:.2%})"
            )
        kern = s.get("kernel")
        if kern and kern.get("per_chunk"):
            lines.append(
                f"kernel path={kern['path']} "
                f"({kern['n_sparse']} sparse / {kern['n_onehot']} one-hot chunks)"
            )
            lines.extend(self._placement_tree(kern))
        lines.append(
            f"executor kernels={self.config.use_kernels} "
            f"reduce={self.config.reduce_mode} layout={self.config.layout}"
        )
        xh = s.get("cross_host")
        if xh:
            h, c = s["mesh_shape"]
            lines.append(
                f"mesh {h}x{c} (hosts x cores/host): modeled cross-host "
                f"{xh['cross_host_bytes']:,.0f}B vs flat all-gather "
                f"{xh['flat_allgather_bytes']:,.0f}B "
                f"({xh['reduction_vs_flat']:.1f}x reduction, "
                f"{xh['bucket_entries']} bucket entries)"
            )
        if self.config.drift != "none":
            lines.append(f"drift policy={self.config.drift} "
                         f"{self.config.drift_options}")
        if self.config.validation != "clip" or self.config.integrity != "none":
            regions = len(self.manifest.checksums) if self.manifest else 0
            lines.append(
                f"integrity validation={self.config.validation} "
                f"checksums={self.config.integrity}"
                + (f" ({regions} regions)" if regions else "")
            )
        return "\n".join(lines)
