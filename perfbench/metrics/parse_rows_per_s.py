"""The repo's sparse parser alone over pool bytes, timed by the traced run
before its window (host clock around host-only code, at least 0.5 s)."""


def read(ctx):
    if not ctx.extras.get("parser_s"):
        return None
    return ctx.extras["parser_rows"] / ctx.extras["parser_s"]
