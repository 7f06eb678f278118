"""Share of the engine's weight and memo lookups served warm over the
window (``PlacementEngine.stats``, as ``cache_hit_rate()`` counts them),
in percent."""


def read(rec):
    c = rec["cache"]
    hits = c["weight_hits"] + c["shared_hits"]
    total = hits + c["weight_misses"] + c["shared_misses"]
    return 100.0 * hits / total if total else None
