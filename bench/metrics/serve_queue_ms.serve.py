"""Mean time a request waits in the service, from ``submit`` to the
start of its tick's solve [ms]: the program's ``serve.queue_s``
histogram over the window; nothing where the program has none."""


def read(r):
    n, total = r.histogram("serve.queue_s")
    if not n:
        return None
    return 1e3 * total / n
