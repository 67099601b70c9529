"""model.gn_swish_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the GroupNorm + swish passes of the generator (scope ``gn_swish``: the per-image moments and the normalise-and-activate pass of ``ops/norm.GroupNorm``, forward and backward, 60 sites in the published VQGAN), in ops that are NOT convolutions: the join of the traced window with the compiled step's text (``benchmark/scope_time.by_scope``), which the driver ``train_vq`` keeps in ``run["vq_scopes"]``. It sums two parts (``benchmark/fused_scope.tags``): the ops named under the scope, and fusions of elementwise / reduction passes that hold the scope's instructions under another root's name. Convolution fusions outside the scope into which XLA fused the scope's passes (the third tag, ``gn_swish_fused_in_conv``) are left out: every convolution of this generator reads a GN + swish output, so that tag can hold most of the step, and the driver's ``by_scope`` line prints it beside the others. So this is a lower bound of what the passes cost, and the memory-bound part a fused norm + activation kernel would replace. A program without the scope, or a driver without the join, leaves nothing to read.
"""

META = {"name": "model.gn_swish_ms_per_step", "unit": "ms", "layer": "models",
        "moves": "train_img_per_s"}


def scope_ms(run, tags):
    """Device ms a step in the ops the join put under ``tags``; None where
    the run holds no join or the join none of them."""
    scoped = run.get("vq_scopes")
    if not scoped or not scoped.get("executions"):
        return None
    seconds = sum(scoped["scope_s"].get(tag, 0.0) for tag in tags)
    return 1000.0 * seconds / scoped["executions"] if seconds else None


def read(run):
    from benchmark import fused_scope

    return scope_ms(run, fused_scope.tags("gn_swish")[:2])
