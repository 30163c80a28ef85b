"""Programs the engine compiled inside the window (its own counter). Expected 0."""


def read(run):
    return run["window"].get("compiles")
