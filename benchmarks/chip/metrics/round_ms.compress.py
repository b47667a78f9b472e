"""Milliseconds per halo round: the fenced exec.solve time of the
compress batches over their halo rounds (the ``halo_rounds`` tag of
the compress groups).  Times this by ``halo_rounds.compress`` and it
gives ``solve_ms.compress``.  Nothing where the program does not tag
its groups."""


def read(r):
    groups = r.spans_named("service.group", kind="compress")
    rounds = sum(s.tags.get("halo_rounds", 0) for g in groups
                 for s in r.descendants(g, ("engine.compress_group",)))
    if not rounds:
        return None
    solve_us = sum(s.dur_us for g in groups
                   for s in r.descendants(g, ("exec.solve",)))
    return solve_us / 1e3 / rounds
