"""Share of the traced window spent in ``api.compile`` [%]: member
expansion, noise draws and program encoding on the host, from the
``api.compile`` spans of ``repro.obs``."""


def read(r):
    secs = r.span_seconds("api.compile")
    if not secs or r.trace.window_s <= 0:
        return None
    return 100.0 * secs / r.trace.window_s
