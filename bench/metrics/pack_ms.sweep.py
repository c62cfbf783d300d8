"""Host time of repacking new placements per window call [ms]: the
``api.plan.pack`` spans of ``repro.obs``, in which ``pack_placed`` lays
B ragged placement lists onto the ``(B, D, K)`` grid."""


def read(r):
    calls = r.info.get("calls")
    pack = r.span_seconds("api.plan.pack")
    if not calls or not pack:
        return None
    return 1e3 * pack / calls
