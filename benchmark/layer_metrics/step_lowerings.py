"""How often the process lowered the train step, as the program itself
counted (``build.lowerings.hvd_train_step``: a ``jax.monitoring`` listener
that ``hvd.init()`` installs). The harness's explicit ``step.lower()`` is
one; every further one happened inside a ``step(state, batch)`` call,
where no outside clock reaches (ROADMAP D1b: 2 today, 1 once
``init_state`` places the state). Nothing to read in a program that does
not count."""

from benchmark.lib.program import step_builds


def read(run):
    return step_builds().get("lowerings")
