"""Device mesh construction — the substrate for every parallelism strategy.

The framework uses one global ``jax.sharding.Mesh`` with named axes:

- ``data``    data parallelism (per-device batch shards, gradient psum)
- ``fsdp``    ZeRO-style state sharding (parallel/rules.py): batches shard
              over it exactly like ``data``, but optimizer moments / EMA
              (and, behind ``ParallelConfig.fsdp_params``, params) are
              PARTITIONED over it instead of replicated — gather-on-use
              is GSPMD's job via the pjit in/out shardings
- ``spatial`` GSPMD spatial sharding of the image H dimension (large images;
              conv halo exchange handled in ``p2p_tpu.parallel.spatial``)
- ``time``    temporal sequence parallelism for video discriminators

The reference has no distributed layer at all (SURVEY.md §2.3): its only
parallelism is DataLoader worker processes. Here the mesh is first-class and
every train step is jitted over it; XLA inserts the ICI collectives.

On a real multi-host slice call :func:`distributed_init` first (wraps
``jax.distributed.initialize``); on a single host (or the CPU test fixture
with ``--xla_force_host_platform_device_count=8``) meshes are built from the
locally visible devices.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"     # ZeRO state sharding: moments/EMA/params (parallel/rules.py)
SPATIAL_AXIS = "spatial"
TIME_AXIS = "time"
MODEL_AXIS = "model"   # tensor parallelism: conv channel dims (parallel/tp.py)
PIPE_AXIS = "pipe"     # pipeline parallelism: trunk stages (parallel/pp.py)
ALL_AXES = (DATA_AXIS, FSDP_AXIS, SPATIAL_AXIS, TIME_AXIS, MODEL_AXIS,
            PIPE_AXIS)
#: the axes a batch's leading (N) dimension shards over — fsdp devices
#: see distinct samples exactly like data devices; only the STATE layout
#: differs between the two axes
BATCH_AXES = (DATA_AXIS, FSDP_AXIS)


def pcast_varying(x, axes):
    """Cast ``x`` to vary over ``axes`` inside a ``jax.shard_map`` body.

    The vma type system needs replicated constants cast to the varying
    type before they enter axis-varying control flow (scan carries that
    ``axis_index`` flows into). ``lax.pcast`` rejects an axis the value
    already varies over — e.g. ``zeros_like`` of a pipe-sharded quant
    leaf is born ``{V:pipe}`` — so only the missing axes are cast."""
    missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. -1 on the data axis means "all remaining devices"."""

    data: int = -1
    spatial: int = 1
    time: int = 1
    model: int = 1   # tensor-parallel axis (channel dims; parallel/tp.py)
    pipe: int = 1    # pipeline-parallel axis (trunk stages; parallel/pp.py)
    fsdp: int = 1    # ZeRO state-sharding axis (parallel/rules.py)

    def resolve(self, n_devices: int,
                context: str = "") -> tuple[int, int, int, int, int, int]:
        """Concrete per-axis sizes ``(data, fsdp, spatial, time, model,
        pipe)`` for ``n_devices``.

        ``context`` (optional) is appended to the failure diagnostics —
        the elastic-relaunch path passes the topology the checkpoint was
        saved on, so "my relaunch flags don't fit this slice" reads as
        exactly that instead of a bare divisibility error.
        """
        d, f, s, t, m, p = (self.data, self.fsdp, self.spatial, self.time,
                            self.model, self.pipe)
        fixed = f * s * t * m * p
        suffix = f"; {context}" if context else ""
        if d == -1:
            if n_devices % fixed:
                raise ValueError(
                    f"mesh data=-1,fsdp={f},spatial={s},time={t},model={m},"
                    f"pipe={p} cannot resolve: {n_devices} device(s) not "
                    f"divisible by fsdp*spatial*time*model*pipe={fixed} — "
                    f"pick axes whose product divides the device "
                    f"count{suffix}"
                )
            d = n_devices // fixed
        if d * fixed > n_devices:
            raise ValueError(
                f"mesh data={d},fsdp={f},spatial={s},time={t},model={m},"
                f"pipe={p} needs {d * fixed} devices but only {n_devices} "
                f"are available — shrink an axis or use data=-1 (all "
                f"remaining devices){suffix}"
            )
        return d, f, s, t, m, p


def parse_mesh_arg(text: str) -> MeshSpec:
    """The ``--mesh`` flag grammar, shared by every CLI.

    Two forms:

    - positional (legacy): ``data,spatial,time[,model[,pipe]]``
      comma-separated ints — ``2,1,1,2`` is data=2 × model=2;
    - named: ``axis=size[,axis=size...]`` over the full vocabulary
      (``data``/``fsdp``/``spatial``/``time``/``model``/``pipe``), any
      order, unnamed axes default to 1 (data to -1 when omitted) —
      ``data=4,fsdp=2,model=2``. The named form is the only way to
      address the ``fsdp`` axis.

    Raises ``ValueError`` with the offending text; CLIs turn that into
    their usage error.
    """
    text = text.strip()
    if "=" in text:
        sizes = {}
        for part in text.split(","):
            if not part.strip():
                continue
            key, _, val = part.partition("=")
            key = key.strip()
            if key not in ALL_AXES:
                raise ValueError(
                    f"unknown mesh axis {key!r} (have {ALL_AXES})")
            if key in sizes:
                raise ValueError(f"mesh axis {key!r} named twice")
            sizes[key] = int(val)
        spec = MeshSpec(data=sizes.pop(DATA_AXIS, -1), **sizes)
    else:
        vals = [int(v) for v in text.split(",")]
        if len(vals) < 3:   # only model/pipe are optional
            raise ValueError("too few axes")
        while len(vals) < 5:
            vals.append(1)
        if len(vals) > 5:
            raise ValueError("too many axes (use the named form for fsdp)")
        d, s, t, m, p = vals
        spec = MeshSpec(data=d, spatial=s, time=t, model=m, pipe=p)
    for axis in ALL_AXES:
        size = getattr(spec, axis)
        if size < 1 and not (axis == DATA_AXIS and size == -1):
            raise ValueError(
                f"mesh axis {axis}={size}: axes must be >=1 (data may be "
                "-1 = all remaining devices)")
    return spec


class TopologyMismatch(ValueError):
    """An elastic relaunch hit a topology delta the resharded-resume path
    cannot reconcile (classified ``abort`` by
    :func:`classify_topology_delta`), or elastic resume was disabled.
    The message names the saved vs. current topology and what to change."""


def mesh_topology(mesh: Optional[Mesh]) -> dict:
    """The recorded topology block for the checkpoint aux sidecar: the
    facts a relaunch must reconcile against before it can restore.

    JSON-able on purpose — this rides the iterator-state sidecar
    (train/checkpoint.py save_aux), not the Orbax tree."""
    sizes = {str(a): int(s) for a, s in dict(mesh.shape).items()} \
        if mesh is not None else {}
    return {
        "process_count": int(jax.process_count()),
        "device_count": int(mesh.size) if mesh is not None
        else len(jax.devices()),
        "mesh": sizes,
    }


def describe_topology(topo: dict) -> str:
    """One-line human form of a topology block (for diagnostics/logs)."""
    mesh = topo.get("mesh") or {}
    axes = ",".join(f"{a}={mesh[a]}" for a in mesh) or "none"
    return (f"{topo.get('process_count', '?')} process(es) x "
            f"{topo.get('device_count', '?')} device(s), mesh [{axes}], "
            f"global_batch={topo.get('global_batch', '?')}")


@dataclasses.dataclass(frozen=True)
class TopologyDelta:
    """Classification of a saved-vs-current topology difference.

    ``kind``:
    - ``"same"``    identical topology — the plain exact-step resume path
    - ``"reshard"`` a compatible delta (process count, data/fsdp/
      spatial/time axis widths, device count): restore proceeds with
      target shardings derived for the NEW mesh, and the per-host data
      skip re-derives from the global step
    - ``"migrate"`` a delta that is lawful only THROUGH a restore-time
      state transform (p2p_tpu.resilience.reshape): ``chain`` names the
      transforms, in application order — ``batch_rebase`` (global-batch
      change: step/epoch/LR basis re-derived from cumulative samples),
      ``pp_restructure`` (pipe-width change: trunk merge + re-split),
      ``tp_amax_recalibrate`` (TP-width change under delayed-int8 amax
      state: closed-form max/broadcast scale remap), ``dtype_cast``
      (explicit, logged dtype-policy cast — opt-in via
      ``--cast_on_restore``)
    - ``"abort"``   a genuinely unreconcilable delta (dtype policy
      without the cast opt-in, ``int8_delayed`` on/off — the TrainState
      TREE differs, no cast fixes that): fail with instructions
    """

    kind: str
    reason: str
    #: migrate-only: transform names, in the order reshape.py applies them
    chain: tuple = ()


def classify_topology_delta(saved: dict, current: dict,
                            has_quant_state: bool = False,
                            cast_on_restore: bool = False) -> TopologyDelta:
    """Reconcile a checkpoint's recorded topology block against the
    relaunch's. Rules (the narrow, auditable core of elastic resume):

    - ``global_batch`` change → migrate (``batch_rebase``): the step
      counter stops naming a sample position, so step/epoch position,
      ``steps_per_epoch``, the LR-schedule basis, and the loader's skip
      arithmetic are re-derived from the sidecar's cumulative
      ``samples_seen`` — accounting stays gapless in SAMPLES.
    - ``mixed_precision``/``moment_dtype`` change → migrate
      (``dtype_cast``) when ``cast_on_restore`` (the ``--cast_on_restore``
      opt-in): the cast is explicit and logged, optimizer moments follow
      the migration policy table, and the integrity manifest is
      regenerated post-cast; WITHOUT the opt-in → abort (Orbax would
      silently cast, changing numerics without a trace).
    - ``int8_delayed`` change → abort always: the TrainState TREE
      differs (quant collections appear/disappear) — not a cast.
    - ``pipe`` width change → migrate (``pp_restructure``): the
      stage-stacked trunk merges back to the flat trunk and re-splits at
      the new width (pipe→no-pipe and no-pipe→pipe are the degenerate
      cases), optimizer moments preserved.
    - ``model`` (TP) width change under delayed-int8 quant state →
      migrate (``tp_amax_recalibrate``): amax is a max statistic, so the
      resharding law is closed-form (ops/int8.reshard_amax).
    - any other mesh-axis / process-count / device-count change →
      reshard (params are replicated or rule-resharded over these axes;
      the input pipeline re-derives per-host shards from the global
      step). The ``fsdp`` axis deliberately rides this row: an
      fsdp↔replicated delta is a pure LAYOUT change — the Orbax load
      lands the moments/EMA on the new mesh's rule-derived target
      shardings (parallel/rules.py), no state transform needed.

    Keys absent from ``saved`` (older sidecars) are treated as matching —
    forward-compatible by construction.
    """
    def differs(key):
        if key not in saved:
            return False
        a, b = saved[key], current.get(key)
        if key == "moment_dtype":
            # None IS float32 (the optimizer default, train/state.py):
            # an explicit --moment_dtype float32 against an unset save
            # (or vice versa) is a spelling difference, not a cast
            a, b = a or "float32", b or "float32"
        return a != b

    chain = []
    reasons = []
    if differs("global_batch"):
        chain.append("batch_rebase")
        reasons.append(
            f"the global batch size changed "
            f"({saved.get('global_batch')} -> "
            f"{current.get('global_batch')}) — step/epoch position and "
            "the LR-schedule basis re-derive from cumulative samples")
    for key, what in (("mixed_precision", "the mixed-precision policy"),
                      ("moment_dtype", "the Adam moment storage dtype")):
        if differs(key):
            if not cast_on_restore:
                return TopologyDelta(
                    "abort",
                    f"{what} changed ({saved.get(key)} -> "
                    f"{current.get(key)}) — restore would silently cast "
                    "the state; relaunch with the original dtype flags, "
                    "or opt in to an explicit, logged cast with "
                    "--cast_on_restore")
            if "dtype_cast" not in chain:
                chain.append("dtype_cast")
            reasons.append(
                f"{what} changed ({saved.get(key)} -> "
                f"{current.get(key)}) — cast on restore "
                "(--cast_on_restore)")
    if differs("int8_delayed"):
        return TopologyDelta(
            "abort",
            "the delayed-int8 policy changed — the TrainState tree "
            "differs (quant collections), which no cast reconciles; "
            "relaunch with the original --int8_delayed")
    # A sidecar with no "mesh" key at all (pre-elastic) recorded nothing
    # to reconcile mesh-wise — skip the axis comparisons. An EMPTY
    # recorded mesh (a single-device save) is different: relaunching onto
    # a real mesh is a legitimate reshard.
    has_saved_mesh = "mesh" in saved
    saved_mesh = saved.get("mesh") or {}
    cur_mesh = current.get("mesh") or {}

    def axis(block, name):
        return int(block.get(name, 1))

    if has_saved_mesh:
        if axis(saved_mesh, PIPE_AXIS) != axis(cur_mesh, PIPE_AXIS):
            chain.append("pp_restructure")
            reasons.append(
                f"the pipeline-parallel width changed "
                f"({axis(saved_mesh, PIPE_AXIS)} -> "
                f"{axis(cur_mesh, PIPE_AXIS)}) — the stacked trunk "
                "merges and re-splits at the new width")
        if axis(saved_mesh, MODEL_AXIS) != axis(cur_mesh, MODEL_AXIS) \
                and has_quant_state:
            chain.append("tp_amax_recalibrate")
            reasons.append(
                f"the tensor-parallel width changed "
                f"({axis(saved_mesh, MODEL_AXIS)} -> "
                f"{axis(cur_mesh, MODEL_AXIS)}) under delayed-int8 amax "
                "state — stored scales remap by the closed-form max law")
    changed = [k for k in ("process_count", "device_count")
               if differs(k)]
    if has_saved_mesh:
        changed += [f"mesh.{a}" for a in set(saved_mesh) | set(cur_mesh)
                    if axis(saved_mesh, a) != axis(cur_mesh, a)]
    if chain:
        if changed:
            reasons.append("topology delta: " + ", ".join(sorted(changed)))
        return TopologyDelta("migrate", "; ".join(reasons),
                             chain=tuple(chain))
    if changed:
        return TopologyDelta(
            "reshard", "topology delta: " + ", ".join(sorted(changed)))
    return TopologyDelta("same", "identical topology")


def make_mesh(
    spec: MeshSpec = MeshSpec(),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the global mesh.

    Axis order is (data, fsdp, spatial, time, model, pipe) with data
    outermost: JAX lays devices out so the *innermost* axes are
    nearest-neighbor on the ICI torus, which is where the bandwidth-hungry
    halo exchanges (spatial), ring shifts (time), and pipeline stage
    hand-offs (pipe: neighbor ppermute every tick) live; data-parallel
    all-reduces tolerate the longer hops. ``fsdp`` sits right under
    ``data``: its param/moment all-gathers and reduce-scatters are the
    next-chattiest collectives after the inner-axis exchanges.
    """
    devices = list(devices if devices is not None else jax.devices())
    d, f, s, t, m, p = spec.resolve(len(devices))
    n = d * f * s * t * m * p
    dev_array = np.asarray(devices[:n]).reshape(d, f, s, t, m, p)
    return Mesh(dev_array, axis_names=ALL_AXES)


def single_device_mesh() -> Mesh:
    return make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])


def distributed_init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host barrier/init. No-op when running single-process."""
    if num_processes is None or num_processes <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Canonical sharding for NHWC image batches: N over (data, fsdp) —
    fsdp devices consume distinct samples like data devices — H over
    spatial."""
    return NamedSharding(mesh, P(BATCH_AXES, SPATIAL_AXIS, None, None))


def video_sharding(mesh: Mesh) -> NamedSharding:
    """NTHWC video batches: N over (data, fsdp), T over time, H over
    spatial."""
    return NamedSharding(
        mesh, P(BATCH_AXES, TIME_AXIS, SPATIAL_AXIS, None, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


_ACTIVE_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "p2p_tpu_active_mesh", default=None
)


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    """Expose ``mesh`` to layers traced within this context.

    The parallel step builders (p2p_tpu.parallel.dp) enter this around the
    step body so ops that need manual sharding regions — the Pallas
    InstanceNorm, which GSPMD would otherwise wrap in a full all-gather of
    the activations (custom calls have no partitioning rule) — can wrap
    themselves in ``shard_map`` over the active mesh at trace time.
    """
    token = _ACTIVE_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _ACTIVE_MESH.reset(token)


def current_mesh() -> Optional[Mesh]:
    """The mesh made visible by :func:`mesh_context`, or None."""
    return _ACTIVE_MESH.get()


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    """Per-host batch for the input pipeline (global / number of processes)."""
    n_proc = jax.process_count()
    if global_batch % n_proc:
        raise ValueError(f"global batch {global_batch} not divisible by {n_proc} hosts")
    del mesh
    return global_batch // n_proc


def spatial_shard_mesh(x) -> Optional[Mesh]:
    """The visible mesh (:func:`current_mesh`) when it spans several
    devices and the NHWC tensor ``x`` can be laid out
    ``P((data, fsdp), spatial, None, None)`` on it — the one test every op
    asks before it wraps itself in a ``shard_map`` over the mesh (the
    Pallas norm kernels; the reflect pad's halo where ``spatial`` > 1);
    else None. A mesh whose ``spatial`` axis is 1 qualifies: Mosaic
    refuses a kernel outside a ``shard_map`` in ANY multi-device
    program, and the moments' psum over an axis of one is free."""
    mesh = current_mesh()
    if mesh is None or mesh.size <= 1:
        return None
    d = 1
    for a in BATCH_AXES:
        d *= mesh.shape.get(a, 1)
    if x.shape[0] % d or x.shape[1] % mesh.shape.get(SPATIAL_AXIS, 1):
        return None
    return mesh
