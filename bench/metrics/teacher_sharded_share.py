"""Share of the clients of the teacher's stacked groups that ran sharded
over the mesh's ``clients`` axis: the program's counters
``ensemble.sharded_clients`` over it plus ``ensemble.replicated_clients``
(repro.obs, counted on the host when the teacher is traced). 100 when
the mesh shards every stacked group; a count of clients, not a time.
None where the program keeps no such counters or ran without a mesh."""


def read(run):
    if run.traffic["driver"] != "stage2":
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    counters = obs.snapshot()["counters"]
    sharded = counters.get("ensemble.sharded_clients", 0)
    total = sharded + counters.get("ensemble.replicated_clients", 0)
    return 100.0 * sharded / total if total else None
