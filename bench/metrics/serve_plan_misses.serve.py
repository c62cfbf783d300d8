"""Plans the service compiled inside the window [misses]: the increase
of ``serve.plan.miss``, all keys.  Set-up warms every plan the traffic
can reach, so it should read 0; nothing where no tick ran."""


def read(r):
    if not r.histogram("serve.tick.batch")[0]:
        return None
    return r.counter("serve.plan.miss")
