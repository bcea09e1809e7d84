"""Runtime tile jobs (``rt.stats()["total_jobs"]``) executed in the window
per frame whose logits were returned.  Moves ``frames_per_s``."""


def read(ctx):
    if not ctx.get("frames"):
        return None
    return ctx["runtime_jobs"] / ctx["frames"]
