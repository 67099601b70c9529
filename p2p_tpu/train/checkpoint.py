"""Checkpointing — Orbax, one pytree, exact round-trip.

The reference's checkpointing is broken as shipped: the saver writes
``{epoch, state_dict_g, state_dict_c}`` (train.py:514-524) while the loader
demands eight keys including D/optimizers/schedulers (train.py:110-116 —
KeyError on any real checkpoint, SURVEY Q4), and test.py expects a pickled
module under a filename train.py never writes (Q5). Here the WHOLE
TrainState (all params, BN stats, spectral u/v, all three optimizer states,
step) is one Orbax pytree: what is saved is what is restored, verified
bitwise by tests/test_train.py::test_checkpoint_roundtrip.

Orbax gives async save (non-blocking on TPU), restore-to-sharding (pass the
mesh-placed abstract state and arrays land already sharded), and retention
policies — the TPU-native story for the failure-recovery subsystem
(SURVEY §5.3/5.4).

Resilience wiring (p2p_tpu.resilience): save/restore run under the
exponential-backoff retry policy ``CKPT_POLICY`` with chaos points at the
``ckpt_save``/``ckpt_restore`` seams, and :meth:`CheckpointManager.
save_aux`/:meth:`restore_aux` keep a tiny JSON sidecar per step — the
data-iterator state (epoch, in-epoch batch position, aug seed) that makes
a mid-epoch checkpoint resumable to the EXACT sample (train/loop.py
maybe_resume). The sidecar lives in a SIBLING ``<dir>.aux/`` directory:
Orbax owns the checkpoint directory's layout, and a foreign subdir there
would trip its step scan.

Integrity + last-good (the self-healing subsystem, resilience/health.py):
every save records a per-array CRC32 manifest (``<step>.integrity.json``
in the aux dir); :meth:`restore` verifies the restored leaves against it
and, when the requested step is corrupt (torn upload, truncated array,
bit rot — or the ``ckpt_corrupt`` chaos seam), transparently falls back
to the newest INTACT older step instead of crashing. A directory with no
intact step raises :class:`CheckpointCorrupt` — deliberately NOT in the
retry layer's transient class: re-reading rotten bytes forever is the
failure mode this error exists to prevent. :meth:`mark_good` /
:meth:`last_good_step` track the newest *eval-validated* step — the
recovery ladder's rollback target.
"""

from __future__ import annotations

import json
import os
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

from p2p_tpu.resilience.chaos import FaultInjected, chaos_point
from p2p_tpu.resilience.retry import CKPT_POLICY, retry_call
from p2p_tpu.train.state import TrainState


class CheckpointCorrupt(RuntimeError):
    """No intact checkpoint could be restored (checksum mismatches or
    unreadable steps all the way down). Classified NON-retryable by
    design: the retry layer handles transient faults, and corrupt bytes
    on disk do not heal with backoff."""

    def __init__(self, directory: str, tried: List[int],
                 last_error: Optional[BaseException] = None):
        self.directory = directory
        self.tried = list(tried)
        # surface the underlying failure in the message itself: when every
        # step fails the SAME way (e.g. a template/shape mismatch from a
        # wrong CLI flag) the cause is the diagnosis, not disk rot
        cause = f"; last error: {last_error!r}" if last_error else ""
        super().__init__(
            f"no intact checkpoint under {directory} "
            f"(tried steps {tried}){cause}; if every step failed "
            "identically, check the restore template/flags before "
            "suspecting corruption")


def _abstract(leaf):
    return ocp.utils.to_shape_dtype_struct(leaf)


class SidecarCorrupt(RuntimeError):
    """Every iterator-state sidecar in scope failed to parse (torn
    half-writes, bit rot) — the checkpoint directory's recorded topology
    is unrecoverable. Deliberately an ERROR rather than a None return:
    a None here would read downstream as "pre-elastic checkpoint,
    nothing to reconcile" and silently bypass the must-abort topology
    classification."""

    def __init__(self, directory: str, newest_step: int):
        self.directory = directory
        self.newest_step = newest_step
        super().__init__(
            f"every checkpoint sidecar under {directory}.aux is "
            f"torn/unreadable (newest attempted step: {newest_step}) — "
            "the run's recorded topology cannot be reconciled; inspect "
            "the .aux directory (restore a sidecar from backup, or "
            "delete the aux dir to resume with step-derived position "
            "AND pre-elastic topology semantics)")


def peek_topology(directory: str) -> Optional[Dict[str, Any]]:
    """The newest step's recorded topology block from ``<directory>.aux``,
    without constructing a :class:`CheckpointManager` (which would create
    directories). Used by the trainers to enrich mesh-resolve failures on
    relaunch: "your --mesh doesn't fit this slice; the checkpoint was
    saved on <topology>". None when no sidecar names one (fresh run, or
    pre-elastic sidecars that parse but record no topology block).

    Raises :class:`SidecarCorrupt` when sidecars EXIST but every one of
    them fails to parse — an all-torn aux dir must not read as
    "pre-elastic" (the None a caller would misinterpret as nothing to
    reconcile)."""
    aux_dir = os.path.abspath(directory) + ".aux"
    try:
        names = os.listdir(aux_dir)
    except OSError:
        return None
    steps = []
    for n in names:
        stem, dot, ext = n.partition(".")
        if dot and ext == "json" and stem.isdigit():
            steps.append(int(stem))
    torn = 0
    for s in sorted(steps, reverse=True):
        try:
            with open(os.path.join(aux_dir, f"{s}.json")) as f:
                topo = json.load(f).get("topology")
        except (OSError, json.JSONDecodeError):
            torn += 1
            continue
        if topo:
            return topo
    if steps and torn == len(steps):
        raise SidecarCorrupt(os.path.abspath(directory), max(steps))
    return None


def _leaf_checksums(tree: Any) -> Optional[Dict[str, Dict[str, Any]]]:
    """``{leaf_path: {crc32, shape, dtype}}`` over a pytree's arrays.

    CRC32 (zlib — fast, and torn/truncated/bit-rotted arrays are the
    threat model, not an adversary) over the host bytes of every leaf.
    None on multi-process runs: a global array's rows are only partially
    addressable per process, so a host-local checksum would not name a
    well-defined value. (Single-process sharded states — CLI-TP — are
    fully addressable and checksum fine.)
    """
    if jax.process_count() > 1:
        return None
    out: Dict[str, Dict[str, Any]] = {}
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in leaves:
        arr = np.ascontiguousarray(np.asarray(leaf))
        out[jax.tree_util.keystr(path)] = {
            "crc32": zlib.crc32(arr.tobytes()),
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
        }
    return out


# --------------------------------------------------------- quant compat
# Forward-compatible restore for GROWING 'quant' collections (ISSUE 14):
# a pre-drain checkpoint is missing the amax leaves the widened int8
# coverage added (new QuantConv sites, the kn2row head, quant_c as a
# whole). Restoring it through a new-config template would be an Orbax
# structure error; instead restore() intersects the template's quant
# trees with the checkpoint's actual structure (item_metadata — no array
# reads), restores what exists, and GRAFTS the template's init values
# onto the missing leaves. The trainer then arms the --recalibrate_steps
# frozen-scale warmup over the mixed collections
# (resilience/reshape.arm_quant_init_warmup) — init-batch scales are
# exactly how a fresh run starts, so the warmup semantics carry over.

_QUANT_FIELDS = ("quant_g", "quant_d", "quant_c")


class _QuantUnreconcilable(Exception):
    """Checkpoint quant structure is not a subset of the template's
    (e.g. a DOWNGRADE: more leaves on disk than in the config) — fall
    back to the plain restore and its loud structure error."""


def _quant_leaf_paths(tree, prefix=()) -> List[Tuple[str, ...]]:
    out: List[Tuple[str, ...]] = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.extend(_quant_leaf_paths(tree[k], prefix + (str(k),)))
    elif tree is not None:
        out.append(prefix)
    return out


def _shape_to_saved(tmpl, saved, path, missing):
    """Template subtree reshaped to the SAVED structure; template leaves
    absent on disk are dropped and recorded in ``missing``."""
    if saved is None:
        missing.extend(_quant_leaf_paths(tmpl, path))
        return None
    if not isinstance(saved, dict):
        if isinstance(tmpl, dict) or tmpl is None:
            raise _QuantUnreconcilable(path)
        return tmpl
    if not isinstance(tmpl, dict):
        raise _QuantUnreconcilable(path)
    out = {}
    for k, sv in saved.items():
        if k not in tmpl:
            raise _QuantUnreconcilable(path + (str(k),))
        out[k] = _shape_to_saved(tmpl[k], sv, path + (str(k),), missing)
    for k, tv in tmpl.items():
        if k not in saved:
            missing.extend(_quant_leaf_paths(tv, path + (str(k),)))
    return out


def _graft_union(restored, tmpl):
    """Union of a restored (pruned) quant tree with the template — the
    missing leaves take the template's (init) values."""
    if restored is None:
        return tmpl
    if not isinstance(tmpl, dict) or not isinstance(restored, dict):
        return restored
    out = dict(restored)
    for k, v in tmpl.items():
        out[k] = _graft_union(out.get(k), v) if k in out else v
    return out


def reconcile_quant_template(template, shardings, saved_meta):
    """``(template', shardings', missing)``: the restore template with
    quant leaves absent from the checkpoint pruned (shardings pruned
    identically), plus the missing leaf paths for the post-restore
    graft. Covers ``quant_g/quant_d/quant_c`` and the PP-stacked trunk's
    ``pp_stages['quant']``. Raises :class:`_QuantUnreconcilable` when
    the checkpoint's quant structure is not a template subset."""
    missing: List[Tuple[str, ...]] = []
    t_upd, s_upd = {}, {}
    for f in _QUANT_FIELDS:
        t_upd[f] = _shape_to_saved(getattr(template, f, None),
                                   saved_meta.get(f), (f,), missing)
        if shardings is not None:
            s_upd[f] = _shape_to_saved(getattr(shardings, f, None),
                                       saved_meta.get(f), (f,), [])
    tmpl_pp = getattr(template, "pp_stages", None)
    saved_pp = saved_meta.get("pp_stages")
    if (isinstance(tmpl_pp, dict) and "quant" in tmpl_pp
            and isinstance(saved_pp, dict)):
        t_upd["pp_stages"] = {
            **tmpl_pp,
            "quant": _shape_to_saved(tmpl_pp.get("quant"),
                                     saved_pp.get("quant"),
                                     ("pp_stages", "quant"), missing),
        }
        sh_pp = getattr(shardings, "pp_stages", None) \
            if shardings is not None else None
        if isinstance(sh_pp, dict) and "quant" in sh_pp:
            s_upd["pp_stages"] = {
                **sh_pp,
                "quant": _shape_to_saved(sh_pp.get("quant"),
                                         saved_pp.get("quant"),
                                         ("pp_stages", "quant"), []),
            }
    if not missing:
        return template, shardings, []
    template = template.replace(**t_upd)
    if shardings is not None and s_upd:
        shardings = shardings.replace(**s_upd) \
            if hasattr(shardings, "replace") else shardings
    return template, shardings, missing


def _restore_arg(abstract_leaf):
    """ArrayRestoreArgs carrying the template's dtype (Orbax casts, which
    is what full restore does too) and sharding when the template names
    one — the TP serving path restores shards directly into place."""
    sharding = getattr(abstract_leaf, "sharding", None)
    return ocp.ArrayRestoreArgs(
        restore_type=jax.Array,
        dtype=abstract_leaf.dtype,
        sharding=sharding,
    )


class CheckpointManager:
    """Thin wrapper over ocp.CheckpointManager for TrainState pytrees."""

    def __init__(self, directory: str, max_to_keep: Optional[int] = None,
                 registry=None):
        directory = os.path.abspath(directory)
        os.makedirs(directory, exist_ok=True)
        self.directory = directory
        self._aux_dir = directory + ".aux"
        # retry/chaos counters land here (None = the process default
        # registry); the trainers pass their run's registry so checkpoint
        # retries show up in the run's own metrics stream
        self._registry = registry
        self._mgr = ocp.CheckpointManager(
            directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
        )
        # the step the last restore() ACTUALLY returned — differs from the
        # requested/latest step when integrity fallback walked to an older
        # one; callers doing step bookkeeping (resume position, rollback
        # target) must read this, not the step they asked for
        self.last_restored_step: Optional[int] = None
        # quant amax leaf paths the last restore() INITIALIZED from the
        # template because the (pre-drain) checkpoint did not carry them
        # — the trainer arms the frozen-scale warmup off this
        # (resilience/reshape.arm_quant_init_warmup)
        self.last_restore_initialized_quant: List[str] = []

    def _reg(self):
        if self._registry is None:
            from p2p_tpu.obs import get_registry

            self._registry = get_registry()
        return self._registry

    def save(self, step: int, state: TrainState, wait: bool = False) -> None:
        def _save():
            chaos_point("ckpt_save", step=step)
            self._mgr.save(step, args=ocp.args.StandardSave(state))
            if wait:
                self._mgr.wait_until_finished()

        # A step the manager ALREADY holds is skipped by Orbax (silently
        # or with a ValueError depending on version): the original bytes
        # stand, so the original integrity manifest must stand too —
        # rewriting it with THIS call's (possibly drifted) values would
        # read as corruption at the next restore.
        wrote = int(step) not in (self._mgr.all_steps() or [])
        # retry the transient failures (FS blips, injected chaos); a step
        # the manager already holds — e.g. a retry racing an async save
        # that DID land — is success, not an error
        try:
            retry_call(_save, policy=CKPT_POLICY, seam="ckpt_save",
                       registry=self._registry)
        except ValueError:
            if step not in (self._mgr.all_steps() or []):
                raise
        # per-array save-time checksums — restore() verifies against these
        # and falls back past a corrupt step (resilience/health.py). The
        # values fetched here are exactly the arrays handed to Orbax above,
        # so the manifest names the checkpoint's true content even while
        # an async save is still flushing. The fetch is deliberately
        # SYNCHRONOUS: the trainer's next dispatch donates (deletes) these
        # buffers, so a worker-thread checksum would race use-after-free —
        # the D2H cost lands once per epoch_save interval, not per step.
        sums = _leaf_checksums(state) if wrote else None
        if sums is not None:
            self._write_aux_json(
                f"{int(step)}.integrity.json",
                {"step": int(step), "algo": "crc32", "leaves": sums})

    def _saved_structure(self, step: int) -> Optional[Dict[str, Any]]:
        """The saved tree's STRUCTURE (field-name dict of nested dicts /
        array metadata, no array reads) for the quant-compat
        reconciliation. Goes through a ``PyTreeCheckpointer`` aimed at
        the step's item directory — the manager's own ``item_metadata``
        only works after a same-process save registered the handler.
        Orbax 0.11 returns a ``StepMetadata`` whose ``item_metadata.tree``
        is that dict. None when the step has no item directory (the
        restore then raises its own not-found error)."""
        item_dir = os.path.join(str(self._mgr.directory), str(step),
                                "default")
        if not os.path.isdir(item_dir):
            return None
        with ocp.PyTreeCheckpointer() as ckptr:
            return ckptr.metadata(item_dir).item_metadata.tree

    def restore(self, state_template: TrainState,
                step: Optional[int] = None, verify: bool = True,
                fallback: Optional[bool] = None, shardings=None):
        """Restore into the structure/sharding of ``state_template``.

        ``step=None`` restores the newest step; the restored leaves are
        verified against the save-time checksum manifest, and a corrupt
        (or unreadable) step FALLS BACK to the next older step — a torn
        final upload costs one checkpoint interval, not the run. An
        EXPLICITLY named step disables the fallback by default (silently
        serving different weights than the operator pinned would be worse
        than failing); the rollback path opts back in with
        ``fallback=True``. Raises :class:`CheckpointCorrupt`
        (non-retryable) when nothing intact remains in scope,
        ``FileNotFoundError`` when the step (or any step) is absent.

        ``shardings`` (a NamedSharding pytree matching the template)
        switches on the RESHARDED restore: the elastic-relaunch path
        (train/loop.py ``plan_elastic_restore``) passes target shardings
        derived for the NEW mesh — rule-driven, parallel/rules.py — and
        Orbax performs the cross-topology load, landing every leaf
        already laid out for the relaunch's topology rather than the
        (possibly dead) one that wrote the checkpoint. Counted on
        ``resharded_restore_total``.
        """
        if fallback is None:
            fallback = step is None
        steps = sorted(int(s) for s in (self._mgr.all_steps() or []))
        if step is not None:
            if int(step) not in steps:
                # an explicitly named step that is ABSENT is a caller
                # error (wrong --step / wrong directory) — silently
                # serving an older checkpoint would be worse than failing
                raise FileNotFoundError(
                    f"no checkpoint at step {step} (have {steps})")
            steps = [s for s in steps if s <= int(step)]
        if not fallback:
            steps = steps[-1:]
        if not steps:
            raise FileNotFoundError("no checkpoint found")

        def build_abstract(tmpl, shards):
            if shards is not None:
                return jax.tree_util.tree_map(
                    lambda leaf, sh: jax.ShapeDtypeStruct(
                        np.shape(leaf) if not hasattr(leaf, "shape")
                        else leaf.shape,
                        getattr(leaf, "dtype", np.asarray(leaf).dtype),
                        sharding=sh),
                    tmpl, shards)
            return jax.tree_util.tree_map(
                ocp.utils.to_shape_dtype_struct, tmpl)

        tried: List[int] = []
        last_exc: Optional[BaseException] = None
        self.last_restore_initialized_quant = []
        for s in reversed(steps):
            tried.append(s)
            # forward-compat quant reconciliation (module comment above):
            # intersect the template's quant trees with THIS step's saved
            # structure; missing leaves restore from the template's init
            # values after the read. Genuinely unreconcilable structures
            # fall back to the plain template — and the plain structure
            # error, which stays the loud failure for every non-quant
            # mismatch. Unreadable metadata marks the step unreadable,
            # exactly like a failed array read below.
            tmpl_s, shards_s = state_template, shardings
            missing: List[Tuple[str, ...]] = []
            try:
                meta = self._saved_structure(s)
            except Exception as exc:  # noqa: BLE001 — fallback ladder
                self._note_corrupt(s, f"metadata unreadable: {exc!r}")
                last_exc = exc
                continue
            if isinstance(meta, dict):
                try:
                    tmpl_s, shards_s, missing = reconcile_quant_template(
                        state_template, shardings, meta)
                except _QuantUnreconcilable:
                    tmpl_s, shards_s, missing = (state_template,
                                                 shardings, [])
            abstract = build_abstract(tmpl_s, shards_s)

            def _restore(s=s, abstract=abstract):
                chaos_point("ckpt_restore", step=s)
                return self._mgr.restore(
                    s, args=ocp.args.StandardRestore(abstract))

            try:
                restored = retry_call(_restore, policy=CKPT_POLICY,
                                      seam="ckpt_restore",
                                      registry=self._registry)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as exc:  # noqa: BLE001 — classified below
                # transient classes already got their CKPT_POLICY retries;
                # whatever still raises here marks THIS step unreadable —
                # fall back rather than die on a torn latest step
                self._note_corrupt(s, f"restore failed: {exc!r}")
                last_exc = exc
                continue
            if verify:
                bad = self._verify_integrity(s, restored)
                if bad:
                    self._note_corrupt(
                        s, "checksum mismatch: " + ", ".join(bad[:3])
                        + ("..." if len(bad) > 3 else ""))
                    continue
            self.last_restored_step = s
            if missing:
                # graft the template's init values onto the amax leaves
                # this (pre-drain) checkpoint does not carry; the caller
                # reads last_restore_initialized_quant and arms the
                # --recalibrate_steps frozen-scale warmup
                updates = {
                    f: _graft_union(getattr(restored, f),
                                    getattr(state_template, f))
                    for f in _QUANT_FIELDS
                }
                if (isinstance(getattr(restored, "pp_stages", None), dict)
                        and isinstance(state_template.pp_stages, dict)
                        and "quant" in state_template.pp_stages):
                    updates["pp_stages"] = {
                        **restored.pp_stages,
                        "quant": _graft_union(
                            restored.pp_stages.get("quant"),
                            state_template.pp_stages["quant"]),
                    }
                restored = restored.replace(**updates)
                self.last_restore_initialized_quant = [
                    "/".join(p) for p in missing]
            if shardings is not None:
                # counted only on SUCCESS — the audit counter must name
                # resharded restores that happened, not ones attempted
                self._reg().counter("resharded_restore_total").inc()
            return restored
        raise CheckpointCorrupt(str(self._mgr.directory), tried,
                                last_error=last_exc) from last_exc

    def _verify_integrity(self, step: int, restored: Any) -> List[str]:
        """Leaf paths whose bytes do not match the save-time manifest
        (empty = intact or unverifiable). Leaves whose dtype/shape differ
        from the recorded ones are skipped — a cast restore (e.g. an old
        f32-moment checkpoint into a bf16-moment template) legitimately
        changes bytes and is not corruption."""
        manifest = self._read_aux_json(f"{int(step)}.integrity.json")
        if not manifest or "leaves" not in manifest:
            return []  # pre-integrity checkpoint: restore unverified
        try:
            chaos_point("ckpt_corrupt", step=int(step))
        except FaultInjected:
            return ["<chaos:ckpt_corrupt>"]
        actual = _leaf_checksums(restored)
        if actual is None:  # multi-process: not checksummable
            return []
        bad = []
        recorded = manifest["leaves"]
        for path, rec in recorded.items():
            a = actual.get(path)
            if (a is None or a["dtype"] != rec["dtype"]
                    or a["shape"] != rec["shape"]):
                continue
            if a["crc32"] != rec["crc32"]:
                bad.append(path)
        return bad

    def _note_corrupt(self, step: int, reason: str) -> None:
        reg = self._reg()
        reg.counter("ckpt_corrupt_total").inc()
        reg.record({"kind": "ckpt_corrupt", "step": int(step),
                    "reason": reason[:500]}, force=True)
        print(f"WARNING: checkpoint step {step} failed integrity "
              f"({reason}) — falling back to the previous intact step",
              flush=True)

    def verify_integrity(self, step: int, restored: Any) -> List[str]:
        """Verify any restored (sub)tree against ``step``'s save-time
        manifest; returns the mismatched leaf paths (empty = intact or
        unverifiable). Leaves absent from ``restored`` (a params-only
        subtree) or with a different recorded shape/dtype (a cast
        restore) are skipped. The serving hot-swap path
        (p2p_tpu.serve.tenancy) verifies exactly the subtree it is about
        to swap in, so a torn/bit-rotted upload is rejected BEFORE it
        replaces live weights — the old engine keeps serving."""
        return self._verify_integrity(int(step), restored)

    def integrity_manifest(self, step: int) -> Optional[Dict[str, Any]]:
        """The save-time (or migration-regenerated) integrity manifest
        for ``step`` — {step, algo, leaves: {path: {crc32, shape,
        dtype}}} — or None when the step predates integrity tracking.
        The dtype-cast migration (resilience/reshape.py) diffs restored
        leaves against it to LOG exactly what a cast changed."""
        return self._read_aux_json(f"{int(step)}.integrity.json")

    def rewrite_integrity(self, step: int, state: Any,
                          note: str = "") -> None:
        """Regenerate ``step``'s integrity manifest from ``state`` — the
        dtype-cast migration epilogue: after an explicit cast the on-disk
        manifest names the PRE-cast bytes, so verification would silently
        skip every cast leaf forever; re-deriving it from the post-cast
        state restores meaningful CRC checks for subsequent restores
        (which read the same on-disk bytes and cast the same way).
        No-op on multi-process runs (leaves only partially addressable —
        same rule as the save-time manifest)."""
        sums = _leaf_checksums(state)
        if sums is None:
            return
        payload = {"step": int(step), "algo": "crc32", "leaves": sums}
        if note:
            payload["migrated"] = note
        self._write_aux_json(f"{int(step)}.integrity.json", payload)

    # -- last-good tracking (the recovery ladder's rollback target) -------
    def mark_good(self, step: int) -> None:
        """Mark ``step`` eval-validated (the PSNR sweep came back finite):
        the recovery ladder rolls back to the NEWEST marked step, so a
        rollback lands on weights that provably evaluated, not merely on
        whatever checkpoint happens to be latest."""
        self._write_aux_json(f"{int(step)}.good.json", {"step": int(step)})

    def last_good_step(self) -> Optional[int]:
        """Newest ``mark_good`` step that still exists on disk, else None."""
        steps = {int(s) for s in (self._mgr.all_steps() or [])}
        good = []
        try:
            names = os.listdir(self._aux_dir)
        except OSError:
            return None
        for n in names:
            if n.endswith(".good.json"):
                try:
                    s = int(n.split(".", 1)[0])
                except ValueError:
                    continue
                if s in steps:
                    good.append(s)
        return max(good) if good else None

    # -- iterator-state sidecar (exact-step resume) -----------------------
    def _write_aux_json(self, name: str, payload: Dict[str, Any]) -> None:
        """Atomically write a JSON sidecar (tmp + rename — a kill
        mid-write must never leave a torn sidecar that poisons the next
        resume/verify)."""
        os.makedirs(self._aux_dir, exist_ok=True)
        path = os.path.join(self._aux_dir, name)
        tmp = path + f".tmp.{os.getpid()}"

        def _write():
            with open(tmp, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, path)

        retry_call(_write, policy=CKPT_POLICY, seam="ckpt_save",
                   registry=self._registry)

    def _read_aux_json(self, name: str) -> Optional[Dict[str, Any]]:
        """Sidecar JSON, or None when absent — or when PRESENT but
        unparseable. The atomic tmp+rename write should make torn
        sidecars impossible, but a hard kill can still half-write on
        filesystems without atomic rename (or leave bit rot): a corrupt
        sidecar degrades to "missing" — resume falls back to the
        position derived from the step counter (epoch-boundary exact,
        mid-epoch best-effort) instead of dying on JSONDecodeError —
        and the degradation is COUNTED (``aux_corrupt_total`` + a
        ``kind="aux_corrupt"`` record), never silent."""
        path = os.path.join(self._aux_dir, name)
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                return json.load(f)
        except json.JSONDecodeError as exc:
            reg = self._reg()
            reg.counter("aux_corrupt_total").inc()
            reg.record({"kind": "aux_corrupt", "file": name,
                        "reason": repr(exc)[:200]}, force=True)
            print(f"WARNING: checkpoint sidecar {name} is corrupt "
                  f"({exc}) — treating as missing (resume falls back to "
                  "step-derived position)", flush=True)
            return None
        except OSError:
            return None

    def save_aux(self, step: int, payload: Dict[str, Any]) -> None:
        """Atomically write the iterator-state JSON sidecar for ``step``."""
        self._write_aux_json(f"{int(step)}.json", payload)

    def restore_aux(self, step: int) -> Optional[Dict[str, Any]]:
        """The sidecar saved with ``step``, or None (pre-resilience
        checkpoints have none — resume falls back to derived state)."""
        return self._read_aux_json(f"{int(step)}.json")

    def restore_subtree(self, template: Any, step: Optional[int] = None):
        """Restore ONLY the subtree(s) named by ``template`` from a full
        checkpoint — the params-only serving restore.

        ``template`` is any pytree whose top-level structure is a sub-dict
        of the saved TrainState's (e.g. an :class:`~p2p_tpu.train.state.
        InferState`): leaves present in the template are read from disk
        (cast to the template dtype, placed on the template sharding);
        everything absent — discriminator, optimizer moments, pool — is
        never materialized, host or device. Pinned bitwise-equal to
        full-restore-then-slice, and to a fraction of the restore
        footprint, by tests/test_serve.py.
        """
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError("no checkpoint found")
        # The manager's own handler registry is StandardSave/Restore-only,
        # so partial restore goes through a PyTreeCheckpointer aimed at the
        # step's item directory (StandardSave writes item name 'default').
        item_dir = os.path.join(str(self._mgr.directory), str(step),
                                "default")
        if not os.path.isdir(item_dir):
            raise FileNotFoundError(f"no checkpoint item at {item_dir}")
        # struct.PyTreeNode templates restore through their field-name dict
        # (the structure StandardSave recorded); None/empty fields (no
        # compression net, no quant scales) hold no arrays and must not
        # reach the reader — they keep their template value.
        import dataclasses

        is_node = dataclasses.is_dataclass(template)
        fields = (
            {f.name: getattr(template, f.name)
             for f in dataclasses.fields(template)}
            if is_node else dict(template)
        )
        want = {k: v for k, v in fields.items()
                if jax.tree_util.tree_leaves(v)}
        abstract = jax.tree_util.tree_map(_abstract, want)
        restore_args = jax.tree_util.tree_map(_restore_arg, abstract)
        # Orbax 0.11: partial_restore reads exactly the leaves ``item``
        # names and never touches the rest of the on-disk tree
        with ocp.PyTreeCheckpointer() as ckptr:
            restored = ckptr.restore(
                item_dir,
                args=ocp.args.PyTreeRestore(
                    item=abstract,
                    restore_args=restore_args,
                    partial_restore=True,
                ),
            )
        out = dict(fields)
        out.update({k: restored[k] for k in want})
        return type(template)(**out) if is_node else out

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()
