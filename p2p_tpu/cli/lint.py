"""Static-analysis frontend — ``python -m p2p_tpu.cli.lint --strict``.

The standing CI correctness+performance gate (docs/STATIC_ANALYSIS.md).
Eight analyzers share one findings format and fail the gate on any
unwaived finding:

1. **AST rules** over every module of ``p2p_tpu/`` (traced randomness,
   ``jax.debug`` outside obs, hot-loop host syncs, CLI↔config flag drift).
2. **Collective-consistency checker** (analysis/collective_consistency):
   host-side collectives (the preempt-agreement allgather, eval stat
   combines, registry aggregation) reachable under per-host-divergent
   predicates or after divergent early exits — the multi-host-hang lint.
3. **Concurrency race lint** (analysis/concurrency_lint): signal-handler
   reentrancy, unlocked shared-state mutation in lock-owning classes,
   atexit-vs-thread shutdown ordering.
4. **Sharding audit**: the declarative rule tables (parallel/rules.py —
   THE partitioner for the whole TrainState since ISSUE 15) statically
   verified against full-size preset TrainStates built shape-only via
   ``jax.eval_shape``. Every family audits against its predicate-rule
   TP table (zero tp-diff gaps — drained) AND against the composed
   TP+FSDP table on an fsdp-bearing mesh; dead/shadowed fsdp rules fail
   like any other.
5. **Memory audit** (analysis/memory_audit): donation markers on the
   lowered train steps (a declared-donated leaf with no alias/donor
   marker is copied, not donated), the serving dead-restore check, and —
   with ``--memory-budget PATH`` — the per-config×mesh HBM budget table
   written as a JSON artifact (CI uploads it).
6. **jaxpr lint**: the traced-program set — tiny-config eval forward,
   GAN train step (plus a sentinel-enabled variant exercising the
   resolved-callback allow list), the video trainer step, and (given ≥2
   devices) the pipelined ``build_pp_train_step`` program — walked for
   host callbacks, f32 dot/conv leaks under the declared bf16 policy,
   and collectives under ``lax.cond``.
7. **Roofline cost model** (analysis/hlo_cost): per-program FLOPs /
   bytes-moved / arithmetic-intensity over the traced set, published as
   the ``perf_budget.json`` artifact via ``--perf-budget PATH``
   (``memory_budget.json``'s twin) with canonical-row bounds asserted
   (``perf-roofline-out-of-bounds``).
8. **Performance audit** (analysis/perf_audit): the fusion-gap lint
   (``perf-unfused-norm-chain`` over a ``P2P_TPU_FORCE_PALLAS``-traced
   fused program), the collective-overlap audit
   (``perf-serialized-collective`` over the overlap-scheduled PP
   program), and the delayed-int8 coverage worklist (``--int8-diff``,
   mirroring ``--tp-diff``). ISSUE 14 DRAINED the worklist: it audits
   the full-coverage program (``train_step[facades_int8_full]`` =
   ``core.config.int8_full_coverage``, the same override set the
   ``facades_int8_full`` sweep row measures) where every conv/dot is
   either quantized or carries a dated in-source waiver (measured-
   rejected stems/head, per-form dispatch-table backward islands) — CI
   asserts "0 sites" so a lost quantized route or an unknobbed new
   layer reappears as a live worklist line and fails the gate.

Waivers: ``# p2p-lint: disable=<rule> -- reason`` in source (findings
carry eqn source locations, so even jaxpr findings waive in-source); the
waiver COUNT is printed via the ONE shared formatter
(``findings.waiver_summary_line`` — exactly once per run, on the OK and
FAIL paths alike; CI greps the phrase) and tests pin a ceiling so it can
only go down.

Exit codes: 0 clean (waived-only), 1 unwaived findings, 2 analyzer crash.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import traceback


def _ensure_fake_devices() -> None:
    """Give the CPU platform 8 fake devices BEFORE jax initializes, so
    the mesh-bearing traced programs (PP) lint everywhere the CLI runs.
    A no-op when jax is already imported (tests set this in conftest)."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="p2p_tpu static-analysis gate")
    p.add_argument("--strict", action="store_true",
                   help="fail on warnings too (the CI mode); default "
                        "fails on errors only")
    p.add_argument("--format", type=str, default="text",
                   choices=["text", "json"],
                   help="findings output format")
    p.add_argument("--tp-diff", action="store_true", dest="tp_diff",
                   help="also print the sharding auditor's tp-vs-rule-"
                        "table migration worklist (ROADMAP item 3), one "
                        "line per leaf")
    p.add_argument("--int8-diff", action="store_true", dest="int8_diff",
                   help="also print the delayed-int8 coverage worklist "
                        "(ROADMAP item 2, DRAINED by ISSUE 14): every "
                        "conv/dot still contracting in bf16/f32 inside "
                        "the full-coverage int8 program without a dated "
                        "waiver, one line per source site — 0 is the "
                        "gated state")
    p.add_argument("--perf-budget", type=str, default=None,
                   dest="perf_budget", metavar="PATH",
                   help="ALSO write the static roofline table "
                        "(per-program FLOPs / bytes / arithmetic "
                        "intensity over the traced set) to PATH as JSON "
                        "— the CI artifact; canonical rows outside their "
                        "declared bands join the report as warnings")
    p.add_argument("--skip-jaxpr", action="store_true",
                   help="skip the (slower) traced-program analyses — "
                        "jaxpr walks AND the donation audit; AST + "
                        "sharding + dead-restore (+ budget table) only")
    p.add_argument("--memory-budget", type=str, default=None,
                   dest="memory_budget", metavar="PATH",
                   help="ALSO compute the per-config×mesh HBM budget "
                        "table (trace-heavy, ~30 s) and write it to PATH "
                        "as JSON — the CI artifact; its over-budget "
                        "findings join the report")
    p.add_argument("--tp-axis-size", type=int, default=2,
                   help="hypothetical model-axis width for the tp diff")
    p.add_argument("--tp-min-ch", type=int, default=512,
                   help="TP pair-rule channel floor for the tp diff")
    return p


def _tiny_cfg(preset: str = "facades", **model_kw):
    """A preset shrunk to trace-size: same code paths, seconds to trace."""
    from p2p_tpu.core.config import get_preset

    cfg = get_preset(preset)
    return dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8, **model_kw),
        data=dataclasses.replace(cfg.data, image_size=16, batch_size=2),
    )


def _sds_tree(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _tiny_batch(cfg, frames: int = 0):
    import numpy as np

    from p2p_tpu.utils.images import dummy_batch

    bs = cfg.data.batch_size
    return dummy_batch(cfg, (bs, frames) if frames else (bs,), np.uint8,
                       abstract=True)


#: the sharding-audit preset set: every family audits (and diffs)
#: against its predicate-rule TP table — zero gaps everywhere is the
#: drained state (ISSUE 13 closed the ResNet/pix2pixHD families; the
#: empty worklist is CI-asserted so a drained family cannot regress).
AUDIT_PRESETS = ("facades", "facades_int8", "edges2shoes_dp",
                 "cityscapes_spatial", "pix2pixhd", "reference")


def run_sharding_audit(report, tp_axis_size: int, tp_min_ch: int):
    """Audit each preset against ITS rule table (family TP tables where
    drained, replicated elsewhere) AND against the composed TP+FSDP
    table on an fsdp mesh (ISSUE 15 — dead/shadowed fsdp rules are lint
    errors like any other); returns the remaining tp-diff worklist."""
    from jax.sharding import PartitionSpec as P

    from p2p_tpu.analysis.sharding_audit import (
        abstract_train_state,
        audit_rules,
        tp_rule_gaps,
    )
    from p2p_tpu.core.config import get_preset
    from p2p_tpu.parallel.rules import (
        REPLICATED_RULES,
        make_fsdp_rules,
        tp_equivalence_rules,
    )

    # the hypothetical target topology: every axis the mesh vocabulary
    # names, sized so divisibility is actually exercised (no devices)
    mesh = {"data": 8, "fsdp": 2, "spatial": 2, "time": 1,
            "model": tp_axis_size, "pipe": 2}
    worklist = []
    for preset in AUDIT_PRESETS:
        cfg = get_preset(preset)
        rules = tp_equivalence_rules(cfg, tp_axis_size, tp_min_ch) \
            or REPLICATED_RULES
        state = abstract_train_state(cfg)
        report.extend(audit_rules(rules, state, mesh))
        # the composed layout the fsdp trainers actually run: the
        # family's TP pairs first, then the ZeRO state rules (params
        # included — the stricter table), then the catch-all
        fsdp_rules = (rules[:-1]
                      + make_fsdp_rules(2, fsdp_params=True)
                      + ((r".*", P()),))
        report.extend(audit_rules(fsdp_rules, state, mesh))
        wl, findings = tp_rule_gaps(state, rules=rules,
                                    axis_size=tp_axis_size,
                                    min_ch=tp_min_ch)
        for entry in wl:
            entry["preset"] = preset
        worklist.extend(wl)
        report.extend(findings)
    return worklist


def _image_setup():
    """(cfg, abstract state, abstract batch) for the tiny image trainer —
    the ONE construction site shared by the traced analyses."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.train.state import create_train_state

    cfg = _tiny_cfg()
    batch = _tiny_batch(cfg)
    ts = jax.eval_shape(lambda: create_train_state(
        cfg, jax.random.key(0),
        {k: np.zeros(v.shape, v.dtype) for k, v in batch.items()},
        train_dtype=jnp.bfloat16))
    return cfg, _sds_tree(ts), batch


def _video_setup():
    """The video-trainer twin of :func:`_image_setup`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.train.video_step import create_video_train_state

    vcfg = _tiny_cfg("vid2vid_temporal")
    vcfg = dataclasses.replace(
        vcfg, data=dataclasses.replace(vcfg.data, batch_size=1, n_frames=2))
    vbatch = _tiny_batch(vcfg, frames=2)
    vs = jax.eval_shape(lambda: create_video_train_state(
        vcfg, jax.random.key(0),
        {k: np.zeros(v.shape, v.dtype) for k, v in vbatch.items()},
        train_dtype=jnp.bfloat16))
    return vcfg, _sds_tree(vs), vbatch


def run_memory_audit(report, budget_path=None):
    """The trace-free memory checks: the serving dead-restore audit and —
    with ``budget_path`` — the HBM budget table (written as the JSON
    artifact). The donation audit lives with the traced analyses
    (:func:`run_traced_analyses`), where it shares each program's single
    trace."""
    from p2p_tpu.analysis.memory_audit import (
        dead_restore_findings,
        memory_budget_table,
    )

    report.extend(dead_restore_findings())

    if budget_path:
        import json

        rows, findings = memory_budget_table()
        report.extend(findings)
        with open(budget_path, "w") as fh:
            json.dump({"rows": rows}, fh, indent=2)
        print(f"memory budget table: {len(rows)} config×mesh rows -> "
              f"{budget_path}", file=sys.stderr)


def _pp_program(overlap: bool = False):
    """The pipelined train step's jaxpr on a tiny 2-stage mesh, or None
    when fewer than 2 devices are visible (the CLI forces 8 fake CPU
    devices when it owns jax initialization). ``overlap=True`` traces the
    latency-hiding schedule — the variant the collective-overlap audit
    and the roofline table pin."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    if len(jax.devices()) < 2:
        return None
    from p2p_tpu.parallel.pp import pp_split_state
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_pp_train_step

    cfg = _tiny_cfg("reference", n_blocks=4)
    cfg = dataclasses.replace(
        cfg, parallel=dataclasses.replace(cfg.parallel,
                                          pp_overlap=overlap))
    from p2p_tpu.utils.images import dummy_batch

    sample = dummy_batch(cfg, (cfg.data.batch_size,), np.uint8)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                ("data", "pipe"))
    # pp_split_state stacks + places the trunk: a (tiny) concrete state
    state = create_train_state(cfg, jax.random.key(0), sample,
                               train_dtype=jnp.bfloat16)
    pp_state = pp_split_state(state, cfg, mesh)
    step = build_pp_train_step(cfg, mesh, n_micro=2,
                               train_dtype=jnp.bfloat16, jit=False)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in sample.items()}
    return jax.make_jaxpr(step)(_sds_tree(pp_state), batch)


def run_traced_analyses(report, programs=None):
    """The traced-program analyses: jaxpr walks (host callbacks, f32
    leaks under the declared bf16 policy, collectives under ``lax.cond``)
    AND the donation-marker audit — each train-step program is traced
    ONCE (``jit(...).trace``) and both the jaxpr and the lowering come
    from that single trace. ``programs`` (a dict) collects the traced
    jaxprs by row name so the perf analyses / roofline table reuse them
    instead of re-tracing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.analysis.collective_consistency import (
        collectives_under_cond,
    )
    from p2p_tpu.analysis.findings import apply_pragma_waivers
    from p2p_tpu.analysis.jaxpr_lint import (
        f32_leak_findings,
        host_callback_findings,
    )
    from p2p_tpu.analysis.memory_audit import donation_findings
    from p2p_tpu.train.state import create_infer_state
    from p2p_tpu.train.step import build_train_step, make_infer_forward

    findings = []
    programs = {} if programs is None else programs

    def walk(jx, tag, allow=()):
        findings.extend(host_callback_findings(jx, tag=tag, allow=allow))
        findings.extend(f32_leak_findings(jx, tag=tag))
        findings.extend(collectives_under_cond(jx, tag=tag))

    cfg, sds, batch = _image_setup()
    sample = {k: np.zeros(v.shape, v.dtype) for k, v in batch.items()}

    # eval/serving forward (metrics tail included — its f32 quality convs
    # are the known, pragma-waived island in losses/metrics.py)
    ist = jax.eval_shape(lambda: create_infer_state(
        cfg, jax.random.key(0), sample, jnp.bfloat16))
    jx_eval = jax.make_jaxpr(make_infer_forward(cfg, jnp.bfloat16))(
        _sds_tree(ist), batch)
    programs["eval_forward[facades]"] = jx_eval
    walk(jx_eval, tag="eval_forward")

    # the full alternating-GAN train step (debug taps at their defaults:
    # a host callback here would fence every training dispatch) — ONE
    # trace of the jitted, donating step serves walks AND donation audit
    tr = build_train_step(cfg, train_dtype=jnp.bfloat16).trace(sds, batch)
    programs["train_step[facades]"] = tr.jaxpr
    walk(tr.jaxpr, tag="train_step")
    report.extend(donation_findings(tr.lower().as_text(), sds,
                                    tag="train_step", jaxpr=tr.jaxpr))

    # the sentinel-enabled variant: the obs tap's debug_callback is the
    # ONE sanctioned callback — allowed by its RESOLVED target function
    # (obs/taps._on_counts through jax's flat-callback closure and one
    # functools.partial level), so any OTHER callback still flags
    scfg = dataclasses.replace(
        cfg, debug=dataclasses.replace(cfg.debug, nan_sentinel=True))
    walk(jax.make_jaxpr(build_train_step(scfg, train_dtype=jnp.bfloat16,
                                         jit=False))(sds, batch),
         tag="train_step+sentinel", allow=("_on_counts",))

    # the video trainer step (satellite: trace-coverage gap — the video
    # loop's hot path was previously unlinted); same shared-trace shape
    from p2p_tpu.train.video_step import build_video_train_step

    vcfg, vsds, vbatch = _video_setup()
    vtr = build_video_train_step(
        vcfg, train_dtype=jnp.bfloat16).trace(vsds, vbatch)
    programs["video_train_step[vid2vid_temporal]"] = vtr.jaxpr
    walk(vtr.jaxpr, tag="video_train_step")
    report.extend(donation_findings(vtr.lower().as_text(), vsds,
                                    tag="video_train_step",
                                    jaxpr=vtr.jaxpr))

    # the pipelined program (needs >= 2 devices for a real pipe axis)
    pp = _pp_program()
    if pp is not None:
        walk(pp, tag="pp_train_step")
    else:
        print("lint: skipping pp_train_step trace (<2 devices — run with "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              file=sys.stderr)

    report.extend(apply_pragma_waivers(findings))


def _int8_train_program(full: bool = False):
    """The delayed-int8 GAN train step's jaxpr (tiny facades_int8).

    ``full=True`` traces the FULL-COVERAGE variant
    (``core.config.int8_full_coverage`` — every ISSUE-14 knob on, the
    override set of the ``facades_int8_full`` preset): the program the
    drained int8-coverage worklist audits. The plain variant stays the
    roofline row for the shipping preset."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_train_step

    cfg = _tiny_cfg("facades_int8")
    if full:
        from p2p_tpu.core.config import int8_full_coverage

        cfg = int8_full_coverage(cfg)
    batch = _tiny_batch(cfg)
    sds = _sds_tree(jax.eval_shape(lambda: create_train_state(
        cfg, jax.random.key(0),
        {k: np.zeros(v.shape, v.dtype) for k, v in batch.items()},
        train_dtype=jnp.bfloat16)))
    return jax.make_jaxpr(build_train_step(
        cfg, train_dtype=jnp.bfloat16, jit=False))(sds, batch)


def _fused_train_program():
    """The pallas-fused train step's jaxpr: a tiny cityscapes config with
    ``norm=norm_d="pallas_instance"``, traced under
    ``P2P_TPU_FORCE_PALLAS=1`` so the dispatch seam routes to the REAL
    kernel even on a CPU runner — the fusion-gap lint then proves no
    chain silently fell back to the lax reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_train_step

    cfg = _tiny_cfg("cityscapes_spatial", norm="pallas_instance",
                    norm_d="pallas_instance")
    batch = _tiny_batch(cfg)
    sds = _sds_tree(jax.eval_shape(lambda: create_train_state(
        cfg, jax.random.key(0),
        {k: np.zeros(v.shape, v.dtype) for k, v in batch.items()},
        train_dtype=jnp.bfloat16)))
    old = os.environ.get("P2P_TPU_FORCE_PALLAS")
    os.environ["P2P_TPU_FORCE_PALLAS"] = "1"
    try:
        return jax.make_jaxpr(build_train_step(
            cfg, train_dtype=jnp.bfloat16, jit=False))(sds, batch)
    finally:
        if old is None:
            os.environ.pop("P2P_TPU_FORCE_PALLAS", None)
        else:
            os.environ["P2P_TPU_FORCE_PALLAS"] = old


def _ensure_perf_programs(programs):
    """Add the perf traced programs (int8 train step, forced-pallas
    fused step, overlap-scheduled PP step — plus the base eval/train/
    video programs when the jaxpr stage didn't already stash them, so
    ``--skip-jaxpr --perf-budget`` still writes the COMPLETE table) to
    ``programs``, tracing each at most once per run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if "eval_forward[facades]" not in programs \
            or "train_step[facades]" not in programs:
        from p2p_tpu.train.state import create_infer_state
        from p2p_tpu.train.step import build_train_step, make_infer_forward

        cfg, sds, batch = _image_setup()
        sample = {k: np.zeros(v.shape, v.dtype) for k, v in batch.items()}
        ist = jax.eval_shape(lambda: create_infer_state(
            cfg, jax.random.key(0), sample, jnp.bfloat16))
        programs["eval_forward[facades]"] = jax.make_jaxpr(
            make_infer_forward(cfg, jnp.bfloat16))(_sds_tree(ist), batch)
        programs["train_step[facades]"] = jax.make_jaxpr(build_train_step(
            cfg, train_dtype=jnp.bfloat16, jit=False))(sds, batch)
    if "video_train_step[vid2vid_temporal]" not in programs:
        from p2p_tpu.train.video_step import build_video_train_step

        vcfg, vsds, vbatch = _video_setup()
        programs["video_train_step[vid2vid_temporal]"] = jax.make_jaxpr(
            build_video_train_step(vcfg, train_dtype=jnp.bfloat16,
                                   jit=False))(vsds, vbatch)
    if "train_step[facades_int8]" not in programs:
        programs["train_step[facades_int8]"] = _int8_train_program()
    if "train_step[facades_int8_full]" not in programs:
        programs["train_step[facades_int8_full]"] = _int8_train_program(
            full=True)
    if "train_step[cityscapes_pallas]" not in programs:
        programs["train_step[cityscapes_pallas]"] = _fused_train_program()
    if "pp_train_step[reference]" not in programs:
        pp = _pp_program(overlap=True)
        if pp is not None:
            programs["pp_train_step[reference]"] = pp
        else:
            print("lint: skipping pp_train_step perf trace (<2 devices)",
                  file=sys.stderr)
    return programs


def run_perf_analyses(report, programs):
    """Analyzer 8 (analysis/perf_audit): the fusion-gap lint over the
    forced-pallas fused program, the collective-overlap audit over the
    overlap-scheduled PP program, and the delayed-int8 coverage worklist.
    Returns the worklist for ``--int8-diff``."""
    from p2p_tpu.analysis.findings import apply_pragma_waivers
    from p2p_tpu.analysis.perf_audit import (
        int8_coverage,
        serialized_collective_findings,
        unfused_norm_chain_findings,
    )

    _ensure_perf_programs(programs)
    findings = []
    findings.extend(unfused_norm_chain_findings(
        programs["train_step[cityscapes_pallas]"],
        tag="train_step[cityscapes_pallas]"))
    pp = programs.get("pp_train_step[reference]")
    if pp is not None:
        findings.extend(serialized_collective_findings(
            pp, tag="pp_train_step[reference]"))
    # The coverage worklist audits the FULL-COVERAGE program (ISSUE 14
    # drained it): every conv/dot there is either quantized or carries a
    # dated in-source waiver naming its measured-rejected / dispatch-
    # table verdict — waived sites leave the worklist, so "0 sites" is
    # the gate and ANY new bf16/f32 contraction (a lost QuantConv route,
    # a new layer without a knob) reappears as a live worklist line.
    worklist, info = int8_coverage(
        programs["train_step[facades_int8_full]"],
        tag="train_step[facades_int8_full]")
    info = apply_pragma_waivers(info)
    waived_sites = {(f.file, f.line) for f in info if f.waived}
    worklist = [w for w in worklist
                if (w["file"], w["line"]) not in waived_sites]
    report.extend(apply_pragma_waivers(findings))
    report.extend(info)
    return worklist


def run_perf_budget(report, programs, budget_path):
    """Analyzer 7 (analysis/hlo_cost): the static roofline table over
    every traced program, written as the ``perf_budget.json`` artifact
    (``memory_budget.json``'s twin); canonical rows outside their
    declared bands join the report as warnings."""
    import json

    from p2p_tpu.analysis.hlo_cost import CHIP_MODEL, perf_budget_rows

    _ensure_perf_programs(programs)
    rows, findings = perf_budget_rows(sorted(programs.items()))
    report.extend(findings)
    with open(budget_path, "w") as fh:
        json.dump({"chip": CHIP_MODEL, "rows": rows}, fh, indent=2)
    print(f"perf budget table: {len(rows)} roofline rows -> "
          f"{budget_path}", file=sys.stderr)


def run_ast_passes(report):
    """The three AST-family analyzers over ONE package walk and ONE
    parse per module (each lint_package_* entry point re-walks on its
    own — fine for tests, 3× the IO/parse cost for the gate)."""
    import ast

    from p2p_tpu.analysis.ast_rules import lint_source
    from p2p_tpu.analysis.collective_consistency import (
        lint_collective_source,
    )
    from p2p_tpu.analysis.concurrency_lint import lint_concurrency_source
    from p2p_tpu.analysis.findings import (
        ERROR,
        Finding,
        iter_package_sources,
    )

    for rel, text, err in iter_package_sources():
        if text is None:
            report.add(Finding(rule="ast-unreadable", severity=ERROR,
                               file=rel, message=str(err)))
            continue
        try:
            tree = ast.parse(text)
        except SyntaxError:
            report.extend(lint_source(rel, text))  # emits ast-syntax-error
            continue
        report.extend(lint_source(rel, text, tree=tree))
        report.extend(lint_collective_source(rel, text, tree=tree))
        report.extend(lint_concurrency_source(rel, text, tree=tree))


def main(argv=None) -> int:
    _ensure_fake_devices()
    args = build_parser().parse_args(argv)

    from p2p_tpu.analysis.findings import Report

    try:
        report = Report()
        programs = {}   # traced jaxprs by row name, shared across stages
        run_ast_passes(report)
        worklist = run_sharding_audit(report, args.tp_axis_size,
                                      args.tp_min_ch)
        run_memory_audit(report, budget_path=args.memory_budget)
        int8_worklist = []
        if not args.skip_jaxpr:
            run_traced_analyses(report, programs=programs)
            int8_worklist = run_perf_analyses(report, programs)
        if args.perf_budget:
            run_perf_budget(report, programs, args.perf_budget)
    except Exception:
        traceback.print_exc()
        print("lint: analyzer crashed (exit 2)", file=sys.stderr)
        return 2

    if args.format == "json":
        import json

        payload = json.loads(report.to_json())
        if args.tp_diff:
            # the machine-readable form of the item-3 worklist — the text
            # branch's per-leaf lines, with shapes/specs as fields
            payload["tp_worklist"] = worklist
        if args.int8_diff:
            payload["int8_worklist"] = int8_worklist
        print(json.dumps(payload, indent=2))
    else:
        print(report.render())
        if args.tp_diff:
            print(f"\ntp-diff migration worklist ({len(worklist)} leaves "
                  "still need predicate rules — ROADMAP item 3):")
            for entry in worklist:
                print(f"  [{entry['preset']}] {entry['leaf']} "
                      f"shape={entry['shape']} tp={entry['tp_spec']} "
                      f"table={entry['rule_spec']} ({entry['direction']})")
        if args.int8_diff:
            print(f"\nint8-coverage worklist ({len(int8_worklist)} "
                  "conv/dot sites still contract in bf16/f32 under "
                  "delayed-int8 — ROADMAP item 2):")
            for w in int8_worklist:
                loc = f"{w['file']}:{w['line']}" if w["file"] else "<?>"
                print(f"  [{w['program']}] {w['op']} "
                      f"{tuple(w['dtypes'])} out={tuple(w['out_shape'])} "
                      f"{loc} x{w['eqns']}")
    failing = report.failing(strict=args.strict)
    from p2p_tpu.analysis.findings import waiver_summary_line

    # the ONE waiver-count line (findings.waiver_summary_line — the
    # prometheus_exposition pattern: one formatter, every surface), so
    # the CI grep sees it EXACTLY once per run, pass or fail
    waivers = waiver_summary_line(len(report.waived))
    mode = "strict" if args.strict else "default"
    # json mode keeps stdout machine-parseable: the status line goes to
    # stderr there, stdout in text mode (the CI log greps it)
    status_stream = sys.stderr if args.format == "json" else sys.stdout
    if failing:
        print(f"lint: FAIL ({mode}) — {len(failing)} unwaived "
              f"finding(s), {waivers}", file=status_stream)
        return 1
    print(f"lint: OK ({mode}) — 0 unwaived findings, {waivers}, "
          f"tp worklist {len(worklist)} leaves, int8 worklist "
          f"{len(int8_worklist)} sites",
          file=status_stream)
    return 0


if __name__ == "__main__":
    sys.exit(main())
