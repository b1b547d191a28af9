"""A number from /debug/engine as read after the window: ``path`` inside
one engine's document, or the largest over all engines."""


def _dig(doc, path):
    for key in path:
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc if isinstance(doc, (int, float)) and not isinstance(doc, bool) else None


def read(ctx, params):
    engines = ctx.engine_after
    if params.get("engine"):
        engines = {params["engine"]: engines.get(params["engine"], {})}
    found = [v for v in (_dig(e, params["path"]) for e in engines.values()) if v is not None]
    return float(max(found)) if found else None
