"""Device ms per traced step under the ``moe/shared_expert`` scope: the
shared experts' SwiGLU on every token; forward, recompute and backward."""


def read(run):
    if run.layer_ms is None:
        return None
    return run.layer_ms.get("moe/shared_expert")
