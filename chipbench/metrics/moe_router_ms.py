"""Device ms per traced step under the ``moe/router`` scope: the sigmoid
router, its balance statistics and the update of the router's bias;
forward, recompute and backward."""


def read(run):
    if run.layer_ms is None:
        return None
    return run.layer_ms.get("moe/router")
