"""model.spade_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in ops that do work of the scope ``spade`` (the SPADE sites of the generator: the shared, gamma and beta convolutions, BN0's moments, the normalise-and-modulate pass), forward and backward: the join of the traced window with the compiled step's text (``benchmark/scope_time.by_scope``), which the driver ``train_labels`` keeps in ``run["spade_scope"]``. It sums three parts (``benchmark/fused_scope.tags``): the ops named under the scope; fusions of elementwise / reduction passes that hold the scope's instructions under another root's name; and fusions around a convolution OUTSIDE the scope (a ResBlk's own backward convolution) into which XLA fused the scope's passes (BN0's backward statistics, the modulate's products), whose time is the convolution's and the passes' together. So it is an upper bound of the mechanism's time; the first part alone, which the driver's ``by_scope`` line prints beside the others, is a lower bound. A kernel that takes the passes out of those convolutions lowers it. A program without the scope, or a driver without the join, leaves nothing to read.
"""

META = {"name": "model.spade_ms_per_step", "unit": "ms", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import fused_scope

    scoped = run.get("spade_scope")
    if not scoped or not scoped.get("executions"):
        return None
    seconds = sum(scoped["scope_s"].get(tag, 0.0)
                  for tag in fused_scope.tags("spade"))
    return 1000.0 * seconds / scoped["executions"] if seconds else None
