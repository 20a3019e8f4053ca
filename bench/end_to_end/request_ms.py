"""The whole window over the number of requests answered, for one
closed-loop caller: stalls between requests count."""


def read(ctx):
    if not ctx["records"]:
        return None
    return 1e3 * ctx["elapsed_s"] / len(ctx["records"])
