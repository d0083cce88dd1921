"""Device ms per traced step under the ``dense_ffn`` scope: the leading
dense layer's SwiGLU FFN; forward, recompute and backward."""


def read(run):
    if run.layer_ms is None:
        return None
    return run.layer_ms.get("dense_ffn")
