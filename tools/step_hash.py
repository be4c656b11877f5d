#!/usr/bin/env python3
"""Showing that a program did not change: each cell's step, hashed.

    python tools/step_hash.py [--described] [--tiny] [--workload NAME ...]
        [--tree CHECKOUT]

For each cell of ``BENCHMARK.json`` (or the ``--workload`` names) the step
is built as ``benchmark/lib/harness.py`` builds it (``load_cell``, the
family's ``build``, ``build_step``: imported, not copied), lowered on
shapes (``jax.eval_shape`` of the parameters and of ``dp.init_state``; the
state replicated over the mesh, the batch split over the world axis, where
the harness's steady steps find them) and compiled. Nothing executes. One
JSON line a cell:

``stablehlo``  sha256 of the lowered StableHLO text (no locations)
``hlo``        sha256 of the compiled HLO text as it is
``hlo_bare``   sha256 of the compiled HLO without ``metadata={...}`` and
               without the tables of call sites the metadata indexes
``op_names``   sha256 of the sorted multiset of the HLO's ``op_name``s
``custom_calls``  how many ``tpu_custom_call``s (Pallas kernels) it holds,
               as the harness counts them

Without ``--described`` the program is compiled for the devices that are
there (the chip, through ``chiprun``; a four-chip cell needs four). With
it, for a described ``v5e:2x2`` (``jax.experimental.topologies``), one
device or four by the cell's ``chips``: no chip needed, the TPU's own
compiler, about 12 minutes for the seven cells. ``--tiny`` takes the
files' ``tiny`` sizes. ``--tree`` imports ``horovod_tpu`` and
``benchmark`` from another checkout (``git archive <commit>``), so one
tool hashes parent and change.

Call sites are no part of a program: the tool lowers with
``jax_traceback_in_locations_limit`` 0, so that neither the checkout's
path nor a moved source line reaches the text or a Mosaic kernel's
payload. ``docs/benchmarks.md`` says what equal hashes license.
"""

import argparse
import collections
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")
_CALL_SITE_TABLES = re.compile(
    r"\n(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
    r"(?:\d+ .*\n)*"
)
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def bare_hlo(hlo: str) -> str:
    """Compiled HLO text without its metadata and without the tables of
    call sites the metadata indexes: the instructions alone."""
    return _CALL_SITE_TABLES.sub("\n", _METADATA.sub("", hlo))


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def text_hashes(stablehlo: str, hlo: str) -> dict:
    names = collections.Counter(_OP_NAME.findall(hlo))
    return {
        "stablehlo": _sha(stablehlo),
        "hlo": _sha(hlo),
        "hlo_bare": _sha(bare_hlo(hlo)),
        "op_names": _sha(json.dumps(sorted(names.items()))),
    }


def lower_cell(cell, devices, bench_dir: str):
    """``jax.stages.Lowered`` of the cell's step over ``devices``, which
    may be described: the harness's build, on shapes."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.lib import data as data_lib, harness, resolve
    from horovod_tpu.parallel import dp

    # A flash entry's jitted call keeps its trace, name stack and all:
    # a cell must not inherit the one before it.
    jax.clear_caches()
    hvd.init(devices[:cell.chips])
    try:
        mesh = hvd.mesh()
        traffic = cell.traffic
        family = resolve.load_family(bench_dir, traffic["family"]).build(
            cell.config, traffic
        )
        step, wrapped, _ = harness.build_step(cell, family, hvd, dp, optax)
        params = jax.eval_shape(
            family.init_params, jax.ShapeDtypeStruct((2,), jax.numpy.uint32)
        )
        state = jax.eval_shape(lambda p: dp.init_state(p, wrapped), params)
        batch = data_lib.make_pool(
            traffic["data"], vocab_size=family.vocab_size,
            global_batch=traffic["per_chip_batch"] * cell.chips,
            seq_len=traffic["seq_len"], n_batches=1, seed=0,
        )[0]

        def placed(tree, spec):
            sharding = NamedSharding(mesh, spec)
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=sharding
                ),
                tree,
            )

        return step.lower(placed(state, P()), placed(batch, P(hvd.WORLD_AXIS)))
    finally:
        hvd.shutdown()


def hash_cells(workloads, *, described: bool, tiny: bool, tree: str = REPO):
    """One dict a cell, in the order asked for (all of ``BENCHMARK.json``
    when ``workloads`` is empty)."""
    if tree not in sys.path:
        sys.path.insert(0, tree)
    import jax

    from benchmark.lib import compile_info, harness, resolve

    bench_dir = os.path.join(tree, "benchmark")
    if described:
        from jax.experimental import topologies

        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        ).devices
    else:
        devices = jax.devices()
    names = list(workloads) or [
        w["name"] for w in resolve.load_manifest(tree)["workloads"]
    ]
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        for name in names:
            cell = harness.load_cell(tree, bench_dir, name)
            if tiny:
                cell = harness.tiny(cell)
            if len(devices) < cell.chips:
                raise SystemExit(
                    f"{name} needs {cell.chips} devices, found {len(devices)}"
                )
            lowered = lower_cell(cell, devices, bench_dir)
            hlo = lowered.compile().as_text()
            yield {
                "workload": name, "chips": cell.chips, "tiny": tiny,
                "device": ("v5e:2x2 (described, nothing ran)" if described
                           else devices[0].device_kind),
                **text_hashes(lowered.as_text(), hlo),
                # as the harness counts them for ``expect.pallas_calls``
                "custom_calls": compile_info.count_pallas_calls(hlo),
            }
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", default=[])
    ap.add_argument("--described", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tree", default=REPO)
    args = ap.parse_args(argv)
    for line in hash_cells(
        args.workload, described=args.described, tiny=args.tiny,
        tree=os.path.abspath(args.tree),
    ):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
