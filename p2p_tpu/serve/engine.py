"""The inference engine — AOT-compiled, bucket-batched generator serving.

Layered under both ``cli/infer.py`` (offline test-split inference) and
``cli/serve.py`` (micro-batching frontend). What it fixes over the seed
inference path, in roofline order:

1. **params-only restore** — construction takes an
   :class:`~p2p_tpu.train.state.InferState` (generator + compression-net
   subtree); ``CheckpointManager.restore_subtree`` reads ONLY those arrays
   from the full-TrainState checkpoint, so serving never materializes the
   discriminator or Adam moments (~5× less restore traffic/host memory,
   pinned by tests/test_serve.py) and needs no ``--ndf``/``--pool_size``
   template-rebuild knobs.
2. **shape bucketing + AOT warmup** — every request batch is padded up to
   one of a small set of batch buckets, each ``jit(...).lower().compile()``d
   ONCE at startup (:meth:`InferenceEngine.warmup`); the tail batch of a
   split can never trigger a mid-serve recompile again (exactly one compile
   per bucket, pinned by test). With a ``compilation_cache_dir`` the
   compiled programs persist on disk (core/cache.py), so cold-start pays
   XLA compile only on the first run EVER.
3. **pipelined host I/O** — device dispatch is async; D2H fetch + PNG
   encode run on the :class:`~p2p_tpu.serve.io.AsyncImageWriter` thread
   pool, overlapping device compute. :meth:`InferenceEngine.run` reports a
   fenced breakdown (``infer_sec`` fenced the StepTimer way, ``encode_sec``
   summed worker time, ``wall_sec`` end-to-end) so the overlap — and the
   honest img/s — is measurable, not asserted.
4. **dtype/TP policies** — ``dtype='bf16'`` runs the generator in bf16
   compute (params stay f32); delayed-int8 checkpoints serve with FROZEN
   activation scales (the eval-mode 'quant' collection is read-only);
   a ``model>1`` mesh serves the generator tensor-parallel via the same
   Megatron sharding tree the trainer uses (parallel/tp.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

from p2p_tpu.core.config import Config
from p2p_tpu.serve.io import AsyncImageWriter, chunk_batch, pad_batch, pick_bucket
from p2p_tpu.train.state import InferState
from p2p_tpu.train.step import make_infer_forward


def _resolve_dtype(dtype):
    import jax.numpy as jnp

    if dtype in (None, "f32", "float32"):
        return None
    if dtype in ("bf16", "bfloat16"):
        return jnp.bfloat16
    return jnp.dtype(dtype)


@dataclasses.dataclass
class ServeStats:
    """Fenced timing breakdown for one :meth:`InferenceEngine.run`."""

    n_images: int = 0
    n_batches: int = 0
    infer_sec: float = 0.0    # dispatch→last-device-result, fenced, −RTT
    encode_sec: float = 0.0   # summed writer-thread fetch+encode time
    wall_sec: float = 0.0     # end-to-end including writer drain, −RTT
    img_per_sec: float = 0.0  # n_images / wall_sec — the honest number
    device_img_per_sec: float = 0.0  # n_images / infer_sec
    overlap_sec: float = 0.0  # encode time hidden under device compute
    n_compiles: int = 0
    buckets: Tuple[int, ...] = ()

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["buckets"] = list(self.buckets)
        return {k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in d.items()}


class InferenceEngine:
    """AOT-compiled bucket-batched generator inference.

    ``state`` is the params-only :class:`InferState` (from
    ``CheckpointManager.restore_subtree`` or ``infer_state_from_train``).
    ``buckets`` are the batch sizes compiled at startup (ascending;
    default: just ``cfg.data.test_batch_size``). ``with_metrics`` compiles
    the PSNR/SSIM tail into each bucket program (needs ``target`` in every
    batch); the pure serving frontend runs without it.
    """

    def __init__(
        self,
        cfg: Config,
        state: InferState,
        buckets: Optional[Sequence[int]] = None,
        dtype: Any = "bf16",
        mesh=None,
        tp_min_ch: Optional[int] = None,
        with_metrics: bool = True,
        compilation_cache_dir: Optional[str] = None,
        io_workers: int = 4,
    ):
        if cfg.data.n_frames > 1:
            raise NotImplementedError(
                "InferenceEngine serves image presets; video inference "
                "stays on cli/infer.py's clip path")
        if compilation_cache_dir:
            from p2p_tpu.core.cache import enable_compilation_cache

            enable_compilation_cache(compilation_cache_dir)
        self.cfg = cfg
        self._dtype = _resolve_dtype(dtype)
        self.mesh = mesh
        bs = cfg.data.test_batch_size
        self.buckets: Tuple[int, ...] = tuple(
            sorted(set(int(b) for b in (buckets or (bs,)))))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"bad buckets {self.buckets}")
        self.with_metrics = with_metrics
        self.io_workers = io_workers
        self._fwd = make_infer_forward(cfg, self._dtype,
                                       with_metrics=with_metrics)
        self._compiled: Dict[int, Any] = {}
        self.n_compiles = 0
        self.aot_sec = 0.0

        # --- state placement: replicated, or TP-sharded over `model` ----
        self._state_shardings = None
        self._batch_sharding = None
        if mesh is not None:
            from p2p_tpu.core.mesh import batch_sharding
            from p2p_tpu.parallel.rules import state_target_shardings

            # the ONE partitioner (parallel/rules.py): Megatron TP when
            # the mesh has a model axis, replicated otherwise — serving
            # state has no optimizer, so an fsdp axis leaves it replicated
            # (the catch-all) while batches still shard over it
            self._state_shardings = state_target_shardings(
                state, mesh,
                tp_min_ch=(tp_min_ch if tp_min_ch is not None
                           else cfg.parallel.tp_min_ch))
            state = jax.device_put(state, self._state_shardings)
            self._batch_sharding = batch_sharding(mesh)
        self.state = state

        # host batch spec the buckets are compiled for: uint8 transport
        # when the pipeline ships raw bytes (DataConfig.uint8_pipeline)
        # (a label-map input is uint8 (H, W, 2): utils/images.wire_spec)
        from p2p_tpu.utils.images import wire_spec

        keys = ["input"]
        if cfg.model.use_compression_net or with_metrics:
            keys.append("target")
        self._batch_spec = {k: wire_spec(cfg, k) for k in keys}

    @property
    def batch_keys(self):
        """The batch-dict keys the bucket programs were compiled for."""
        return tuple(self._batch_spec)

    # ------------------------------------------------------------- warmup
    def _abstract_batch(self, bucket_bs: int) -> Dict[str, jax.ShapeDtypeStruct]:
        return {
            k: jax.ShapeDtypeStruct((bucket_bs,) + hwc, dt)
            for k, (hwc, dt) in self._batch_spec.items()
        }

    def _compile_bucket(self, bucket_bs: int):
        from p2p_tpu.core.mesh import mesh_context

        jit_kw = {}
        if self._state_shardings is not None:
            jit_kw["in_shardings"] = (
                self._state_shardings,
                {k: self._batch_sharding for k in self._batch_spec},
            )
        with mesh_context(self.mesh):
            compiled = (
                jax.jit(self._fwd, **jit_kw)
                .lower(self.state, self._abstract_batch(bucket_bs))
                .compile()
            )
        self.n_compiles += 1
        return compiled

    def warmup(self) -> "InferenceEngine":
        """AOT-compile every bucket program now (idempotent). With the
        persistent compilation cache enabled this is a disk load, not an
        XLA compile, on every run but the first."""
        t0 = time.perf_counter()
        for b in self.buckets:
            if b not in self._compiled:
                self._compiled[b] = self._compile_bucket(b)
        self.aot_sec += time.perf_counter() - t0
        return self

    # ----------------------------------------------------------- hot-swap
    def swap_state(self, new_state: InferState, warm: bool = True) -> None:
        """Atomically swap the serving weights under the ALREADY-compiled
        bucket programs — the zero-downtime half of checkpoint hot-swap
        (serve/tenancy.py; docs/SERVING.md "Hot-swap").

        The compiled executables close over shapes/dtypes, not values:
        any state with the identical abstract tree serves through them
        with ZERO new compiles. The swap

        1. REJECTS (ValueError) a state whose structure, shapes or dtypes
           differ from the live one — the old weights keep serving;
        2. places the new tree on device through the engine's shardings
           (the TP path lands shards directly in place) and blocks until
           the H2D transfer completes — the first post-swap request never
           pays the transfer;
        3. with ``warm=True``, runs one zero-batch through the smallest
           compiled bucket, proving the new params EXECUTE against the
           compiled programs before any request can see them (a failure
           here raises and leaves the old state serving);
        4. swaps the state reference — one atomic attribute write, so a
           concurrent in-flight :meth:`infer_batch` (which reads the
           reference once) finishes on the OLD weights and the next
           dispatch sees the new ones. No lock on the serving path.
        """
        old = jax.tree_util.tree_leaves_with_path(self.state)
        new = jax.tree_util.tree_leaves_with_path(new_state)
        if len(old) != len(new):
            raise ValueError(
                f"hot-swap rejected: new state has {len(new)} leaves, "
                f"serving state has {len(old)} — different model family "
                "or EMA/quant policy; start a new tenant instead")
        for (po, lo), (pn, ln) in zip(old, new):
            if po != pn or tuple(lo.shape) != tuple(ln.shape) \
                    or lo.dtype != ln.dtype:
                raise ValueError(
                    "hot-swap rejected: leaf "
                    f"{jax.tree_util.keystr(pn)} is "
                    f"{ln.shape}/{ln.dtype}, serving state has "
                    f"{jax.tree_util.keystr(po)} {lo.shape}/{lo.dtype} — "
                    "the compiled bucket programs cannot serve it")
        if self._state_shardings is not None:
            new_state = jax.device_put(new_state, self._state_shardings)
        else:
            new_state = jax.device_put(new_state)
        jax.block_until_ready(new_state)
        if warm and self._compiled:
            b = min(self._compiled)
            zeros = {k: np.zeros(s.shape, s.dtype)
                     for k, s in self._abstract_batch(b).items()}
            jax.block_until_ready(self._compiled[b](new_state, zeros))
        self.state = new_state

    # ------------------------------------------------------------ serving
    def infer_batch(self, host_batch: Dict[str, np.ndarray]):
        """Pad one host batch to its bucket and dispatch (async). Returns
        ``(pred, metrics, n_real)`` with DEVICE arrays — slice ``[:n_real]``
        to drop the padding rows."""
        if not self._compiled:
            self.warmup()
        n = next(iter(host_batch.values())).shape[0]
        bucket = pick_bucket(n, self.buckets)
        padded, n_real = pad_batch(
            {k: np.asarray(v) for k, v in host_batch.items()
             if k in self._batch_spec},
            bucket,
        )
        pred, metrics = self._compiled[bucket](self.state, padded)
        return pred, metrics, n_real

    def stream(
        self, host_batches: Iterable[Dict[str, np.ndarray]]
    ) -> Iterator[Tuple[Any, Any, int]]:
        """Map :meth:`infer_batch` over an iterator, keeping one dispatch
        in flight ahead of the consumer (double-buffered device feed:
        batch N+1's H2D + compute overlaps the consumer's work on N)."""
        pending = None
        max_bs = self.buckets[-1]
        for host_batch in host_batches:
            for chunk in chunk_batch(host_batch, max_bs):
                out = self.infer_batch(chunk)
                if pending is not None:
                    yield pending
                pending = out
        if pending is not None:
            yield pending

    def run(
        self,
        host_batches: Iterable[Dict[str, np.ndarray]],
        names: Optional[Sequence[str]] = None,
        out_dir: Optional[str] = None,
        collect_metrics: bool = False,
    ) -> Tuple[ServeStats, Dict[str, List[float]]]:
        """The full serving pipeline: bucket → dispatch → threaded D2H +
        PNG encode, with the fenced timing breakdown.

        ``names[i]`` names the i-th REAL image's output file under
        ``out_dir`` (falling back to ``<i>.png``); with ``out_dir=None``
        nothing is written (pure throughput / metrics pass). Fencing
        mirrors the obs StepTimer chained methodology: the dispatch loop
        is fenced ONCE by a host fetch on the last device result, minus
        the measured RTT (obs/timing.py), then credited into a StepTimer
        so img/s means the same thing here as in the metrics stream.
        """
        from p2p_tpu.obs import StepTimer, measure_rtt

        self.warmup()
        writer = AsyncImageWriter(self.io_workers) if out_dir else None
        pending_metrics: List[Tuple[Dict[str, Any], int]] = []
        rtt = measure_rtt()
        timer = StepTimer(batch_size=1)
        stats = ServeStats(buckets=self.buckets)
        t0 = time.perf_counter()
        n_saved = 0
        last = None
        for pred, metrics, n_real in self.stream(host_batches):
            if writer is not None:
                paths = []
                for _ in range(n_real):
                    name = (names[n_saved] if names and n_saved < len(names)
                            else f"{n_saved}.png")
                    paths.append(f"{out_dir}/{name}")
                    n_saved += 1
                # batch-level submit: one worker-side D2H for the whole
                # prediction; padding rows never reach a file
                writer.submit_batch(pred, paths)
            if collect_metrics and metrics:
                # keep the DEVICE arrays + the real count; fetching (or
                # device-slicing) here would fence/recompile mid-loop
                pending_metrics.append((metrics, n_real))
            stats.n_images += n_real
            stats.n_batches += 1
            last = pred
        if last is not None:
            jax.block_until_ready(last)  # fences the in-order device queue
        stats.infer_sec = max(time.perf_counter() - t0 - rtt, 1e-9)
        if writer is not None:
            writer.drain()
            stats.encode_sec = writer.encode_sec
            writer.close()
        stats.wall_sec = max(time.perf_counter() - t0 - rtt, 1e-9)
        timer.credit(stats.n_images, stats.wall_sec)
        stats.img_per_sec = timer.images_per_sec
        stats.device_img_per_sec = stats.n_images / stats.infer_sec
        stats.overlap_sec = max(
            0.0, stats.infer_sec + stats.encode_sec - stats.wall_sec)
        stats.n_compiles = self.n_compiles
        out_metrics: Dict[str, List[float]] = {}
        if collect_metrics and pending_metrics:
            for k in pending_metrics[0][0]:
                out_metrics[k] = np.concatenate([
                    np.asarray(m[k], np.float32).ravel()[:n_real]
                    for m, n_real in pending_metrics
                ]).tolist()
        return stats, out_metrics


def serving_restore_template(cfg: Config,
                             sample_batch: Dict[str, np.ndarray]):
    """The InferState template the serving restore actually reads.

    Template dtype stays None (f32 masters): the checkpoint stores f32
    state and the dtype POLICY is compute-side (make_infer_forward casts)
    — exactly the trainer's mixed-precision stance.

    With EMA serving (``cfg.health.ema_decay`` set), the template keeps
    ONLY the smoothed tree: the engine swaps ``ema_g`` into ``params_g``
    immediately after restore, so also reading ``params_g`` from disk
    would double the generator restore bytes (and hold both trees in
    memory) just to discard one — the ``memory-dead-restore`` finding the
    static-analysis gate pins (p2p_tpu/analysis/memory_audit.py). The
    same helper feeds that auditor, so the two cannot drift."""
    from p2p_tpu.train.state import create_infer_state

    template = create_infer_state(cfg, jax.random.key(0), sample_batch)
    if jax.tree_util.tree_leaves(template.ema_g):
        template = template.replace(params_g=None)
    return template


def engine_from_checkpoint(
    cfg: Config,
    ckpt_dir: str,
    sample_batch: Dict[str, np.ndarray],
    step: Optional[int] = None,
    **engine_kw,
) -> Tuple[InferenceEngine, int]:
    """Template + params-only restore + engine, in one call — the shared
    construction path of cli/infer.py and cli/serve.py. Returns
    ``(engine, restored_step)``."""
    from p2p_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(ckpt_dir)
    try:
        step = step if step is not None else mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint found under {ckpt_dir}")
        template = serving_restore_template(cfg, sample_batch)
        state = mgr.restore_subtree(template, step)
    finally:
        mgr.close()
    if jax.tree_util.tree_leaves(state.ema_g):
        # EMA-trained checkpoint (HealthConfig.ema_decay, requested via
        # the CLI's --ema_decay): serve the SMOOTHED generator — the
        # ProGAN-lineage quality lever. Pinned bitwise == raw at decay=0.
        # The template pruned params_g (serving_restore_template), so the
        # raw tree was never read from disk.
        state = state.replace(params_g=state.ema_g, ema_g=None)
    return InferenceEngine(cfg, state, **engine_kw), int(step)
