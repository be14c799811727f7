"""Device memory of the chosen round program per chip: arguments +
temporaries + outputs - outputs aliased to a donated argument, from its
``memory_analysis``. It decides between the spatial and temporal rounds."""


def read(ctx):
    b = ctx["program_bytes"]
    return None if b is None else b / 1e9
