"""Mean requests a coalesced solve takes, the batch occupancy [rows]:
the program's ``serve.tick.batch`` histogram over the window; nothing
where no tick ran."""


def read(r):
    n, total = r.histogram("serve.tick.batch")
    if not n:
        return None
    return total / n
