"""Compile requests (``jax.monitoring``) inside the warm-up steps after
the first: the step built again for shardings it has itself produced
(ROADMAP D1b: 1 today, 0 once ``init_state`` places the state)."""


def read(run):
    return run["warmup_recompiles"]
