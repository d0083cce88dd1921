"""Device ms per traced step of the latent attention in every layer: the
``mla`` scope (projections, the latent's norm, rotary embedding) and the
causal softmax attention within it (``attention``); forward, recompute and
backward."""


def read(run):
    if run.layer_ms is None or "mla" not in run.layer_ms:
        return None
    return run.layer_ms["mla"] + run.layer_ms.get("attention", 0.0)
