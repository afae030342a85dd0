"""slab_use.repair: percent of RSStream's dispatched slab slots that
held a live segment over the window (cess_rs_slab_segments_total over
cess_rs_slab_slots_total); the rest is padding to the slab shape."""


def read(run):
    slots = run.stages.get("cess_rs_slab_slots_total")
    if not slots:
        return None
    return 100.0 * run.stages["cess_rs_slab_segments_total"] / slots
