"""probe_slot_fill: share of the bitmap-probe slots the svs programs run
over (Jb × Bp × M each, the program's ``probe_slots`` counter) that probe
a real seed posting against a real bitmap (``probe_slots_useful``)."""


def read(run):
    slots = run.counters.get("probe_slots")
    if not slots or "probe_slots_useful" not in run.counters:
        return None
    return 100.0 * run.counters["probe_slots_useful"] / slots
