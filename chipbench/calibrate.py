"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 4,5,6

For each of ``--seeds``: the program's first training steps (built and
compiled as a run builds them; no window) against the float32 reference.
For each of ``--control-seeds``: the reference computed in the precision
below the configuration's put in the program's place (the control,
``reference.CONTROL_DTYPE``), and the reference with half of the training
nodes left out of the loss (a fault), each against the reference.  One
JSON line per reading; one process for all of them.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _numbers(prog, ref):
    from chipbench import reference as R
    return R.compare(prog, ref)


def program_reading(cell, seed):
    from chipbench import harness as H
    t0 = time.perf_counter()
    indptr, indices = H.make_graph(cell.config, cell.traffic, seed)
    prog = H.build_program(cell, seed, indptr, indices)
    got = H.first_steps(prog, cell.config)
    compile_s = prog.compile_s
    del prog
    gc.collect()
    ref = H.reference_readings(cell, seed, indptr, indices)
    return dict(kind="program", seed=seed, compile_s=compile_s,
                seconds=time.perf_counter() - t0, **_numbers(got, ref))


def half_batch(data):
    import jax.numpy as jnp
    keep = (jnp.arange(data["n"]) % 2 == 0).astype(data["mask"].dtype)
    return dict(data, mask=data["mask"] * keep)


def control_readings(cell, seed):
    """The control (bfloat16 reference) and the half-batch fault, each
    against the float32 reference."""
    from chipbench import harness as H
    from chipbench import reference as R
    indptr, indices = H.make_graph(cell.config, cell.traffic, seed)
    params0, data = H.reference_data(cell, seed, indptr, indices)
    mod = H.model_module(cell.config)
    opt = cell.config["optimizer"]
    ref = R.train_readings(mod.reference_logits, params0, data, opt)
    out = []
    for kind, d, dt in ((f"control_{R.CONTROL_DTYPE}", data, R.CONTROL_DTYPE),
                        ("fault_half_batch", half_batch(data), "float32")):
        t0 = time.perf_counter()
        got = R.train_readings(mod.reference_logits, params0, d, opt,
                               dtype=dt)
        out.append(dict(kind=kind, seed=seed,
                        seconds=time.perf_counter() - t0,
                        **_numbers(got, ref)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from chipbench import harness as H
    import jax

    cell = H.load_cell(args.workload)
    H.device_info(cell.chips, require_chip=True)
    seeds = lambda s: [int(v) for v in s.split(",") if v]
    for seed in seeds(args.control_seeds):
        for r in control_readings(cell, seed):
            print(json.dumps(r), flush=True)
        jax.clear_caches()
    for seed in seeds(args.seeds):
        print(json.dumps(program_reading(cell, seed)), flush=True)
        jax.clear_caches()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
