#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and the
planted faults', on many seeds in one process. Not part of a benchmark run.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--faults 7,8,9] [--seconds 8] [--out F]

For every ``--seeds`` seed the program is set up as a run sets it up and
its numbers are compared with the float32 reference, as ``correct`` does:
the largest of these is a limit's lower reading. ``--control-seeds`` reads
the control in the program's place: the reference computed with float8
matmul operands, one step below the bfloat16 the configurations state.
``--faults`` plants, on training cells, faults in the reference put in the
program's place, at the cell's own size and on its chips: half of the batch
left out (the mean taken over the rest), and, where the cell has more than
one chip, the exchange between chips left out (each chip takes the loss
and gradient of its own rows, nothing is summed, the first chip's answer
is read). The smallest control or fault reading is a limit's upper one.
Serving cells run a short window of ``--seconds`` at the cell's own load on
each seed. A training seed not named in ``--seeds`` runs references alone.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench.harness import Cell, Session, load_module  # noqa: E402


def seeds(text: str):
    return [int(x) for x in text.split(",") if x]


def train_readings(drv, s: Session):
    import jax
    trainer, state, ckpt_dir = drv.build(s)
    try:
        state, prog = drv.program_readings(s, trainer, state)
        jax.block_until_ready(state)
    finally:
        trainer.shutdown()
        import shutil
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    del trainer, state
    gc.collect()
    return prog


def half_batch(sound, loss_fn, treedef, mesh):
    """The reference's loss and gradient over the first half of the rows."""
    import jax
    full = sound(loss_fn, treedef, mesh)

    def fn(flat, tokens, labels):
        half = lambda a: jax.device_put(a[:a.shape[0] // 2], a.sharding)
        return full(flat, half(tokens), half(labels))
    return fn


def no_exchange(sound, loss_fn, treedef, mesh):
    """Each device's loss and gradient of its own rows, nothing summed
    across devices; what is read back is the first device's."""
    import jax
    from jax.sharding import PartitionSpec as P
    from chipbench.trainref import ROWS
    tree = lambda leaves: jax.tree_util.tree_unflatten(treedef, leaves)
    local = jax.value_and_grad(lambda p, t, l: loss_fn(tree(p), t, l))
    return jax.jit(jax.shard_map(local, mesh=mesh,
                                 in_specs=(P(), P(ROWS), P(ROWS)),
                                 out_specs=(P(), P()), check_vma=False))


@contextlib.contextmanager
def planted(fault):
    """``fault`` in place of the reference's loss and gradient."""
    from chipbench import trainref
    sound = trainref.grad_fn
    trainref.grad_fn = functools.partial(fault, sound)
    try:
        yield
    finally:
        trainref.grad_fn = sound


def train_cell(c: Cell, args) -> dict:
    from chipbench import trainref
    drv = load_module("drivers", "train")
    faults = {"half_batch": half_batch}
    if c.chips > 1:
        faults["no_exchange"] = no_exchange
    out = {"program": {}, "control": {}, **{k: {} for k in faults}}
    mk = lambda seed: Session(c, seed, args.seconds, False, T_PROCESS,
                              __import__("jax").devices())

    def keep(kind: str, seed: int, gaps: dict) -> None:
        """The readings, and the verdict ``correct`` reaches on them under
        the cell's limits."""
        gaps["correct"] = all(gaps[k] <= lim for k, lim in c.limits.items())
        out[kind][seed] = gaps
        print(kind, seed, gaps, flush=True)

    every = list(dict.fromkeys(args.seeds + args.control_seeds + args.faults))
    for seed in every:
        s = mk(seed)
        prog = train_readings(drv, s) if seed in args.seeds else None
        ref = drv.reference(s)
        if prog is not None:
            keep("program", seed, trainref.compare(prog, ref))
            print("  widest leaves", trainref.widest(prog, ref), flush=True)
        if seed in args.control_seeds:
            keep("control", seed,
                 trainref.compare(drv.reference(s, "fp8"), ref))
        if seed in args.faults:
            for kind, fault in faults.items():
                with planted(fault):
                    keep(kind, seed, trainref.compare(drv.reference(s), ref))
    return out


def serve_cell(c: Cell, args) -> dict:
    import jax
    import numpy as np
    from chipbench import data, weights
    drv = load_module("drivers", "serve")
    ref = load_module("refs", c.config["reference"])
    out = {"program": {}, "control": {}}
    for seed in args.seeds:
        s = Session(c, seed, args.seconds, False, T_PROCESS, jax.devices())
        bb, params, srv = drv.build(s)
        drv.warm(s, srv)
        arrivals = data.serve_schedule(seed, s.seconds, c.traffic["rate_per_s"],
                                       c.traffic["prompt_lens"],
                                       c.traffic["output_lens"],
                                       c.config["vocab_size"])
        reqs, _, _ = drv.serve_window(s, srv, arrivals)
        del bb, params, srv
        gc.collect()
        done = [r for r in reqs if r.done.is_set()]
        out["program"][seed] = {"logit_gap": drv.reference_gap(s, done),
                                "requests": len(done)}
        print("program", seed, out["program"][seed], flush=True)
        if seed in args.control_seeds:
            # the control's own greedy token at each served position
            pick = sorted(done, key=lambda r: -(len(r.prompt) + len(r.out))
                          )[:c.traffic["check_requests"]]
            seqs = [jax.numpy.asarray(np.concatenate(
                [r.prompt, np.asarray(r.out[:-1], np.int32)])) for r in pick]
            rows = [(len(r.prompt) - 1, len(r.out)) for r in pick]
            get = weights.leaf_fn(seed, c.config["model_type"],
                                  jax.numpy.bfloat16)
            f32 = ref.served_logits(get, c.config, seqs, rows, "f32",
                                    c.traffic["ctx"])
            f8 = ref.served_logits(get, c.config, seqs, rows, "fp8",
                                   c.traffic["ctx"])
            gap = 0.0
            for a, b in zip(f32, f8):
                a, b = np.asarray(a), np.asarray(b)
                t = b.argmax(-1)
                gap = max(gap, float((a.max(-1) - a[np.arange(len(t)), t]
                                      ).max()))
            out["control"][seed] = {"logit_gap": gap}
            print("control", seed, out["control"][seed], flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--faults", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("control readings are taken on the chip only", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    c = Cell.find(args.workload)
    fn = train_cell if c.traffic["kind"] == "train" else serve_cell
    out = fn(c, args)
    text = json.dumps(out, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
