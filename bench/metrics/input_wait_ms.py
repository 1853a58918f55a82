"""Mean host time per window step spent getting the input: the program's
``Prefetcher.next()`` plus ``place_batch``, from the benchmark's own
spans around those calls (host clock)."""


def read(ctx):
    fetch, place = ctx.host["fetch"], ctx.host["place"]
    if not fetch:
        return None
    return 1e3 * (sum(fetch) + sum(place)) / len(fetch)
