def _field(facts, ref):
    """``name`` or ``name.field`` (an aggregate pvar's sum or count)."""
    name, _, field = ref.partition(".")
    delta = facts["pvars"].get(name)
    if delta is None:
        return None
    return delta[field] if field else delta


def read(facts, pvars, num, den):
    """100 x the change of ``num`` over the summed changes of ``den``
    across the window; nothing where the denominator did not tick."""
    top = _field(facts, num)
    parts = [_field(facts, d) for d in den]
    if top is None or None in parts or not sum(parts):
        return None
    return 100.0 * top / sum(parts)
