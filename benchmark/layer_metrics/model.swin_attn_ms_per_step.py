"""model.swin_attn_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in window attention (scope ``swin_attn`` of ``p2p_tpu/models/swinir.py``: the qkv projection, the logits with their bias and mask, the softmax, A v and the output projection, forward and backward; 36 layers in the published SwinIR-M): the ops named under the scope in the join of the traced window with the compiled step's text (``benchmark/scope_time.by_scope``), which the driver ``train_sr`` keeps in ``run["sr_scopes"]``. An op is counted under the FIRST of the four scopes ``swin_attn`` / ``swin_window`` / ``swin_mlp`` / ``swin_ln`` in its name, so a fusion XLA names after one of its instructions counts whole under that instruction's scope. A program without the scope, or a driver without the join, leaves nothing to read.
"""

META = {"name": "model.swin_attn_ms_per_step", "unit": "ms",
        "layer": "models", "moves": "train_img_per_s"}


def scope_ms(run, key, scopes):
    """Device ms a step in the ops the join ``run[key]`` put under
    ``scopes``; None where the run holds no such join or the join none of
    them."""
    scoped = run.get(key)
    if not scoped or not scoped.get("executions"):
        return None
    seconds = sum(scoped["scope_s"].get(s, 0.0) for s in scopes)
    return 1000.0 * seconds / scoped["executions"] if seconds else None


def read(run):
    return scope_ms(run, "sr_scopes", ("swin_attn",))
