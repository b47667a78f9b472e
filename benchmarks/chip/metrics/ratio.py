"""Input bytes over container bytes, summed over the compress requests
completed in the window: speed bought with ratio shows here."""


def read(r):
    done = r.completed()
    out = sum(q.out_bytes for q in done)
    return sum(q.in_bytes for q in done) / out if out else None
