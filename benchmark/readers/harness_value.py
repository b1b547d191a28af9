"""A number the harness takes itself: seconds to /readyz, executables
built inside the window."""


def read(ctx, params):
    v = ctx.harness.get(params["key"])
    return None if v is None else float(v)
