"""Time of one tick's solve [ms]: the mean of the ``repro.obs``
``serve.solve`` spans (plan lookup, placement packing, the solve and the
results per request), whichever backend ran them; nothing where the
program has no such span."""


def read(r):
    secs = [ev[3] for ev in r.spans
            if ev[0] == "span" and ev[1] == "serve.solve"]
    if not secs:
        return None
    return 1e-6 * sum(secs) / len(secs)
