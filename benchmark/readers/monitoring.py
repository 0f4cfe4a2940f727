"""Reader ``monitoring``: what jax.monitoring's compile events said.

``field`` is ``setup_compile_s`` (seconds of backend compilation before the
window opened) or ``window_compiles`` (compilations inside the window)."""


def read(run: dict, field: str):
    return run["compile"].get(field)
