"""Halo rounds per field: the ``halo_rounds`` tag of the compress
groups (iterations of the solve's round loop, summed over a group's
device chunks), over the fields of the compress batches.  Nothing
where the program does not tag its groups."""


def read(r):
    groups = r.spans_named("service.group", kind="compress")
    n = sum(int(g.tags.get("n_requests", 1)) for g in groups)
    rounds = [s.tags["halo_rounds"] for g in groups
              for s in r.descendants(g, ("engine.compress_group",))
              if "halo_rounds" in s.tags]
    if not n or not rounds:
        return None
    return sum(rounds) / n
