"""Device-trace profiler for the benchmark training steps.

Captures a TPU trace of the compiled ResNet / BERT training step with
``jax.profiler`` and converts the xplane to per-HLO-op statistics using
the ``xspace_to_tools_data`` converter bundled with TensorFlow — no
TensorBoard UI needed. Prints the top-K ops by self time plus a
category rollup (conv / BN-reduce / elementwise / other), which is the
evidence base for the conv+BN fusion work (VERDICT r2 #1).

Usage:
    python tools/profile_step.py [--model resnet50] [--top 40] [--out-dir DIR]
"""

import argparse
import glob
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def capture(run, args0, logdir):
    import jax

    run(*args0)  # compile outside the trace
    with jax.profiler.trace(logdir):
        out = run(*args0)
        jax.block_until_ready(out)


class ConverterUnavailable(RuntimeError):
    """The xplane→hlo_stats converter (TF's bundled pybind) is absent."""


def _load_converter():
    """TF's ``xspace_to_tools_data`` pybind, or a clear actionable error
    instead of a bare ImportError traceback when TF isn't installed
    (tensorboard_plugin_profile's python shim is version-skewed vs TF
    2.21, so we call the pybind directly)."""
    try:
        from tensorflow.python.profiler.internal import (
            _pywrap_profiler_plugin as pp,
        )
    except ImportError as e:
        raise ConverterUnavailable(
            "per-HLO stats need TensorFlow's bundled xplane converter: "
            "install tensorflow>=2.x (the captured trace itself only needs "
            "jax; re-run with --out-dir DIR to keep the trace and convert "
            "it elsewhere). Original error: " + str(e)
        ) from e
    return pp


def xplane_to_hlo_stats(logdir):
    """Convert the captured .xplane.pb to hlo_stats rows."""
    pp = _load_converter()
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise RuntimeError(f"no xplane.pb under {logdir}")
    raw, _ = pp.xspace_to_tools_data([paths[-1]], "hlo_stats", {})
    return raw


def parse_hlo_stats(raw):
    """hlo_stats arrives as a gviz JSON table; return list of dicts."""
    txt = raw.decode() if isinstance(raw, (bytes, bytearray)) else raw
    # gviz: {"cols": [...], "rows": [{"c": [{"v": ...}, ...]}, ...]}
    m = re.search(r"\{.*\}", txt, re.S)
    tbl = json.loads(m.group(0))
    cols = [c.get("label") or c.get("id") for c in tbl["cols"]]
    rows = []
    for r in tbl["rows"]:
        rows.append({cols[i]: (c or {}).get("v") for i, c in enumerate(r["c"])})
    return rows


# Order matters: first match wins, so the more specific collective
# patterns must precede the bare "reduce" BN bucket.
CATEGORIES = (
    ("allreduce", re.compile(r"all-reduce|allreduce|all-gather|reduce-scatter", re.I)),
    ("conv", re.compile(r"convolution|conv", re.I)),
    ("bn_reduce", re.compile(r"reduce", re.I)),
    ("copy/transpose", re.compile(r"copy|transpose", re.I)),
    ("elementwise", re.compile(r"fusion|add|multiply|select|maximum", re.I)),
)


def categorize(name, category_hint=""):
    blob = f"{name} {category_hint}"
    for label, pat in CATEGORIES:
        if pat.search(blob):
            return label
    return "other"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument(
        "--out-dir",
        help="write the trace here and keep it (e.g. chiprun_out/prof, so "
        "a chip call brings it home); default: a temporary directory",
    )
    ap.add_argument("--json", help="dump all rows (all columns) to this path")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    hvd.init()
    n = hvd.size()
    wa = hvd.WORLD_AXIS

    if args.model == "resnet50":
        import bench

        model = bench.ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        rng = jax.random.PRNGKey(0)
        images = jnp.zeros((n * 128, 224, 224, 3), jnp.bfloat16)
        labels = jnp.zeros((n * 128,), jnp.int32)
        variables = model.init(rng, images[:2], train=True)
        params, batch_stats = variables["params"], variables["batch_stats"]
        opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
        opt_state = opt.init(params)

        def one_step(params, batch_stats, opt_state, images, labels):
            def loss_fn(p):
                logits, updates = model.apply(
                    {"params": p, "batch_stats": batch_stats},
                    images,
                    train=True,
                    mutable=["batch_stats"],
                )
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels
                ).mean()
                return loss, updates["batch_stats"]

            (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            updates, new_opt = opt.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_bs = hvd.fused_allreduce(new_bs, op=hvd.Average)
            return new_params, new_bs, new_opt, hvd.allreduce(loss)

        @hvd.spmd(in_specs=(P(), P(), P(), P(wa), P(wa)), out_specs=(P(), P(), P(), P()))
        def run(params, batch_stats, opt_state, images, labels):
            def body(_, carry):
                p, bs, os_, _loss = carry
                return one_step(p, bs, os_, images, labels)

            return lax.fori_loop(
                0, 5, body, (params, batch_stats, opt_state, jnp.zeros((), jnp.float32))
            )

        args0 = (params, batch_stats, opt_state, images, labels)
    elif args.model == "bert":
        from horovod_tpu.models.bert import BertConfig, BertModel

        batch, seq = 32, 512
        cfg = BertConfig.base()
        model = BertModel(cfg)
        tokens = jnp.zeros((n * batch, seq), jnp.int32)
        targets = jnp.zeros((n * batch, seq), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens[:2])["params"]
        opt = hvd.DistributedOptimizer(optax.adamw(1e-4))
        opt_state = opt.init(params)

        def one_step(params, opt_state, tokens, targets):
            def loss_fn(p):
                logits = model.apply({"params": p}, tokens)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, targets
                ).mean()

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, new_opt = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt, hvd.allreduce(loss)

        @hvd.spmd(in_specs=(P(), P(), P(wa), P(wa)), out_specs=(P(), P(), P()))
        def run(params, opt_state, tokens, targets):
            def body(_, carry):
                p, os_, _loss = carry
                return one_step(p, os_, tokens, targets)

            return lax.fori_loop(
                0, 5, body, (params, opt_state, jnp.zeros((), jnp.float32))
            )

        args0 = (params, opt_state, tokens, targets)
    else:
        raise SystemExit(f"unknown model {args.model}")

    logdir = args.out_dir or tempfile.mkdtemp(prefix="hvdtpu_prof_")
    capture(run, args0, logdir)
    try:
        rows = parse_hlo_stats(xplane_to_hlo_stats(logdir))
    except ConverterUnavailable as e:
        print(f"error: {e}", file=sys.stderr)
        print(f"trace dir (raw xplane): {logdir}", file=sys.stderr)
        raise SystemExit(2)
    if args.out_dir:
        print(f"trace dir: {logdir}", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f)
        print(f"rows dumped to {args.json}", file=sys.stderr)

    # Column names vary slightly across versions; find them dynamically.
    def col(row, *names):
        for nm in names:
            for k in row:
                if k and nm in k.lower():
                    return row[k]
        return None

    stats = []
    for r in rows:
        name = col(r, "hlo op expression", "hlo op name", "op name", "name") or "?"
        cat = col(r, "hlo op category", "category") or ""
        t = col(r, "total self time (us)", "self time", "self-time")
        if t is None:
            continue
        stats.append((float(t), str(name)[:160], str(cat)))
    stats.sort(reverse=True)

    total = sum(t for t, _, _ in stats)
    print(f"\ntotal self time: {total/1e3:.2f} ms over {len(stats)} ops (5 steps)")
    agg = {}
    for t, name, cat in stats:
        agg.setdefault(categorize(name, cat), [0.0, 0])
        agg[categorize(name, cat)][0] += t
        agg[categorize(name, cat)][1] += 1
    print("\ncategory rollup:")
    for k, (t, c) in sorted(agg.items(), key=lambda kv: -kv[1][0]):
        print(f"  {k:16s} {t/1e3:9.2f} ms  ({t/total*100:5.1f}%)  [{c} ops]")
    print(f"\ntop {args.top} ops by self time:")
    for t, name, cat in stats[: args.top]:
        print(f"  {t/1e3:8.3f} ms  [{cat:24s}] {name}")


if __name__ == "__main__":
    main()
