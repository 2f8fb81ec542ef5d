"""Median gap between consecutive tokens of a session over the whole
window (host clock): the typical decode round as a user sees it, a
steadier statistic beside the ``itl_p95_ms`` tail."""


def read(run):
    return run.layer.get("itl_p50_ms")
