"""Serve engine: mean over scheduler iterations of
``scheduler.num_occupied / num_slots`` (read after each ``step()``)."""


def read(ctx):
    loop = ctx["loop"]
    occ = [it[2] for it in loop["iters"] if it[1] <= loop["until_s"]]
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ) / loop["num_slots"]
