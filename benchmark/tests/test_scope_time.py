"""Device time by scope, on the recorded trace of
``benchmark/tools/record_trace.py`` and the text its step compiles to for
a v5e (``data/small_trace.hlo.txt``: the same instruction names as the
trace's events). That step holds a convolution under
``jax.named_scope("net_a")`` (``%fusion``, ``%fusion.3`` and the layout
copies around them) and a tanh under ``net_b`` (``%select_tanh_fusion``,
``%slice_bitcast_fusion``, ``%copy.9``); the layout copy and the pad of
the parameter ``x`` are named after the parameter: unscoped."""

import os

import pytest

from benchmark import scope_time, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "small_trace.xplane.pb")
with open(os.path.join(DATA, "small_trace.hlo.txt")) as f:
    TEXT = f.read()


@pytest.fixture(scope="module")
def scoped():
    return scope_time.by_scope(TRACE, TEXT, ("net_a", "net_b"))


def test_first_scope_looks_through_transforms():
    scopes = ("G", "loss_vgg", "C_branch")
    first = scope_time.first_scope
    assert first("jit(step)/jvp(G)/ExpandNetwork/Conv_0/conv", scopes) == "G"
    assert first("jit(step)/transpose(jvp(loss_vgg))/mul", scopes) == \
        "loss_vgg"
    # the FIRST scope owns the op: G called inside the compression branch
    assert first("jit(step)/transpose(jvp(C_branch))/G/Conv_0/conv",
                 scopes) == "C_branch"
    assert first("jit(step_in_mesh)/jit(_where)/select_n", scopes) is None
    assert first("state.params_g['G']['kernel']", scopes) is None


def test_instructions_are_joined_by_name():
    owner = scope_time.instruction_scopes(TEXT, ("net_a", "net_b"))
    assert scope_time.module_name(TEXT) == "jit_step"
    assert owner["fusion"] == owner["fusion.3"] == owner["copy.7"] == "net_a"
    assert owner["select_tanh_fusion"] == owner["copy.9"] == "net_b"
    assert owner["copy.6"] is None and owner["copy-start"] is None


def test_seconds_by_scope_add_up_to_the_ops_of_the_module(scoped):
    assert scoped["module"] == "jit_step" and scoped["executions"] == 3
    assert scoped["n_op_events"] == 42 and scoped["unmatched_s"] == 0.0
    # the convolution (189.4 us), its input's halo fusion and the three
    # layout copies XLA puts around it
    assert scoped["scope_s"]["net_a"] == pytest.approx(
        (189375 + 19413 + 187330 + 82217 + 988) * 1e-9, rel=1e-6)
    assert scoped["scope_s"]["net_b"] == pytest.approx(
        (10173 + 12340 + 51625) * 1e-9, rel=1e-6)
    assert scoped["scope_s"][scope_time.UNSCOPED] == pytest.approx(
        (55283 + 13966 + 39 + 9 + 20 + 7) * 1e-9, rel=1e-6)
    reduced = trace_reduce.reduce_trace(TRACE)
    assert scoped["op_s"] == pytest.approx(
        sum(reduced["group_s"].values()), rel=1e-9)


def test_per_step_numbers(scoped):
    scoped = dict(scoped, scope_s={"G": 0.3, "D_fake": 0.06, "loss_fm": 0.03,
                                   scope_time.UNSCOPED: 0.01}, op_s=0.4)
    numbers = scope_time.per_step_numbers(scoped)
    assert numbers == {
        "step.unscoped_share": pytest.approx(2.5),
        "model.g_ms_per_step": pytest.approx(100.0),
        "model.d_ms_per_step": pytest.approx(30.0)}


def test_a_program_without_scopes_is_all_unscoped():
    """The parent's step: no scope of the tuple in any op_name (and none
    to import). The share reads 100 and no net has a number."""
    scoped = scope_time.by_scope(TRACE, TEXT, ())
    assert set(scoped["scope_s"]) == {scope_time.UNSCOPED}
    assert scope_time.per_step_numbers(scoped) == {
        "step.unscoped_share": pytest.approx(100.0)}


def test_text_of_another_kind_is_refused():
    with pytest.raises(ValueError, match="HloModule"):
        scope_time.by_scope(TRACE, "module @jit_step {}", ())
