"""Host time of the serving scheduler per engine step: the wall time of the
harness's annotation around each ``ServeEngine.step()`` less the device-busy
time inside it, averaged over the traced steps (ms)."""


def read(ctx):
    tr = ctx["trace"]
    spans = tr.host_spans("chipbench.step")
    if not spans:
        return None
    host = [d * 1e-9 - tr.busy_within(s, s + d) for _, s, d in spans]
    return 1e3 * sum(host) / len(host)
