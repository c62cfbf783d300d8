"""Host time of packing a coalesced solve's placements [ms per solve]:
the ``api.plan.pack`` spans of ``repro.obs`` over the solves the
``serve.tick.batch`` histogram counts, in which ``pack_placed`` lays a
tick's ragged placement lists onto the ``(B, D, K)`` grid; nothing
where no placed solve ran."""


def read(r):
    solves, _ = r.histogram("serve.tick.batch")
    pack = r.span_seconds("api.plan.pack")
    if not solves or not pack:
        return None
    return 1e3 * pack / solves
