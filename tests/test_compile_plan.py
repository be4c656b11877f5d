"""Whole steps compiled for the described TPU v5e: what they plan.

No chip is needed (``conftest.v5e_topology``: libtpu compiles for a
described ``v5e:2x2``, nothing runs). These compile a cell's step at its
real size, which takes minutes: the file sorts early so that the suite's
other files run beside it, and its tests carry ``no_timeout``.
"""

import os

import pytest

import tools.step_hash as step_hash


# What the Olmo-Hybrid cell's step plans with no seam in it: the parent of
# PR 52 (ledger, PR 51, ``peak_hbm_gb`` 14.047800832 on the chip; the same
# program compiled here for the described chip reads the same bytes).
OLMO_PLANNED_WITHOUT_SEAMS = 14_047_800_832


@pytest.mark.no_timeout
def test_update_seams_plan_the_dense_cell_no_higher_than_without(
    v5e_topology,
):
    """Why ``fusion._takes_param_along`` has its shape: a seam also moves
    the compiler's pick among its memory schedules for the whole step.
    The Olmo-Hybrid cell's step as the harness builds it (fourteen seams:
    twelve FFN matrices, the head, the table), compiled for the described
    v5e, must plan no more memory than the step without seams did. With
    every gradient alone in its barrier it planned 3.7% more, every update
    held to the program's end; as committed, 2.5% less (PERF.md section 6,
    PR 52). Nothing runs; one compile of about 80 s."""
    from benchmark.lib import compile_info, harness
    from horovod_tpu.obs import registry

    bench_dir = os.path.join(step_hash.REPO, "benchmark")
    cell = harness.load_cell(
        step_hash.REPO, bench_dir, "Olmo-Hybrid-7B.lm-gdn-s8192"
    )
    compiled = step_hash.lower_cell(
        cell, v5e_topology.devices, bench_dir
    ).compile()
    assert registry.always().gauge("fusion.update_seams").get() == 14
    plan = compile_info.planned_bytes(compiled.memory_analysis())
    assert plan["peak_bytes"] <= OLMO_PLANNED_WITHOUT_SEAMS, plan
