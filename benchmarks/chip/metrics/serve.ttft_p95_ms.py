"""Time to first token, p95 (host clock, from each request's due time)
of the window's requests due after the traced slice has ended: stopping
the profiler holds the serve loop for seconds.  Some 25 requests, so the
second largest; reported beside ``itl_p95_ms`` without a bound."""


def read(run):
    return run.layer.get("ttft_p95_ms")
