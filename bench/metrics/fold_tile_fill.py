"""fold_tile_fill: share of the tile steps a walk of every padded seed row
would take (the program's ``fold_tiles_ceiling`` counter, M / 128 for each
active fold slot) that the fold megakernels walk (``fold_tiles``, the
seed's real tiles)."""


def read(run):
    ceiling = run.counters.get("fold_tiles_ceiling")
    if not ceiling or "fold_tiles" not in run.counters:
        return None
    return 100.0 * run.counters["fold_tiles"] / ceiling
