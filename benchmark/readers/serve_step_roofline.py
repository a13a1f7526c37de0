"""The least time for one top-bucket scoring program (``opcount.
linear_score`` at the bucket's rows) over its device time, percent. The
bucket programs share one name and differ by shape, so the top bucket's is
the one whose executions took most device time in total: above the knee
the batcher fills the top bucket every time."""


def read(ctx):
    r = ctx.reduced
    if not r:
        return None
    stem = ctx.config["score_program"]
    best = None
    for name, secs in r["module_s"].items():
        if name.split("(")[0].startswith(stem):
            if best is None or secs > best[0]:
                best = (secs, r["module_calls"][name])
    if not best or not best[1]:
        return None
    return 100.0 * ctx.facts["top_bucket_least_s"] / (best[0] / best[1])
