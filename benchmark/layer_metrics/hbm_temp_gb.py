"""Gigabytes of temporaries in the compiled step's plan
(``memory_analysis().temp_size_in_bytes``, one device)."""


def read(run):
    return run["built"]["plan"]["temp_bytes"] / 1e9
