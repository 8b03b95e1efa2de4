import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, record memory/cost/collective analysis.

MUST be run as its own process (the device-count flag above is set before
any other import, including jax).  One combo per invocation keeps compile
memory bounded; ``--all`` orchestrates subprocesses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro.configs import INPUT_SHAPES, arch_names, get_config  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import build_program  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.parallel.ctx import activation_sharding  # noqa: E402
from repro.parallel.sharding import ShardingRules, data_axes  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "../../../artifacts/dryrun")


def shardings_for(program, rules: ShardingRules):
    """in_shardings matching each program's argument tuple."""
    if program.name == "train_step":
        aparams, aopt, batch, rng = program.args
        return (
            rules.param_sharding(aparams),
            rules.opt_sharding(aopt),
            rules.batch_sharding(batch),
            rules.replicated(),
        )
    if program.name == "prefill_step":
        aparams, batch = program.args
        return (rules.param_sharding(aparams), rules.batch_sharding(batch))
    aparams, acache, batch = program.args
    return (
        rules.param_sharding(aparams),
        rules.cache_sharding(acache),
        rules.batch_sharding(batch),
    )


def run_one(arch: str, shape_name: str, mesh_kind: str, out_dir: str) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    program = build_program(model, shape, dp=(32 if mesh_kind == "multi" else 16))
    rules = ShardingRules(cfg, mesh, fsdp=(program.name == "train_step"))
    in_sh = shardings_for(program, rules)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "entry": program.name,
        "num_devices": mesh.devices.size,
        "ok": False,
    }
    t0 = time.perf_counter()
    with mesh, activation_sharding(data_axes(mesh)):
        jitted = jax.jit(program.fn, in_shardings=in_sh)
        lowered = jitted.lower(*program.args)
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 2)

    try:
        ca = compiled.cost_analysis()
        rec["cost_analysis"] = {
            k: float(v)
            for k, v in ca.items()
            if isinstance(v, (int, float)) and (
                "flops" in k.lower() or "bytes" in k.lower() or "utilization" not in k.lower()
            )
        }
        rec["flops_per_device"] = float(ca.get("flops", 0.0))
        rec["bytes_per_device"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:  # pragma: no cover
        rec["cost_analysis_error"] = repr(e)

    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(ma, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            )
            if hasattr(ma, k)
        }
    except Exception as e:  # pragma: no cover
        rec["memory_analysis_error"] = repr(e)

    try:
        text = compiled.as_text()
        rec["hlo"] = hlo_analysis.analyze(text)   # loop-aware flops/bytes/collectives
        rec["hlo_chars"] = len(text)
    except Exception as e:  # pragma: no cover
        rec["collective_error"] = repr(e)

    rec["ok"] = True
    rec["total_s"] = round(time.perf_counter() - t0, 2)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def run_all(out_dir: str, meshes=("single", "multi"), resume=True) -> None:
    combos = [
        (a, s, m)
        for a in arch_names()
        for s in INPUT_SHAPES
        for m in meshes
    ]
    for arch, shape, mesh_kind in combos:
        path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}.json")
        if resume and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("ok"):
                    print(f"skip {arch} {shape} {mesh_kind} (done)")
                    continue
        print(f"=== {arch} {shape} {mesh_kind}", flush=True)
        t0 = time.perf_counter()
        proc = subprocess.run(
            [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                "--out", out_dir,
            ],
            env={**os.environ},
            capture_output=True,
            text=True,
            timeout=3600,
        )
        dt = time.perf_counter() - t0
        if proc.returncode != 0:
            print(f"FAIL ({dt:.0f}s):\n{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}")
            os.makedirs(out_dir, exist_ok=True)
            with open(path, "w") as f:
                json.dump(
                    {
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "ok": False, "error": proc.stderr[-4000:],
                    },
                    f, indent=1,
                )
        else:
            print(f"ok ({dt:.0f}s)")


def run_solver_program(
    arch: str, mesh_kind: str, out_dir: str,
    solver: str = "era", nfe: int = 10, batch: int = 32, seq: int = 2048,
    bf16_buffer: bool = False,
) -> dict:
    """Lower the paper's full sampling loop (Algorithm 1) as one program —
    the §Perf target-C artifact."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import ERAConfig, SolverConfig, linear_schedule, sample_program
    from repro.models.diffusion import DiffusionLM

    cfg = get_config(arch).with_(param_dtype=jnp.bfloat16)
    dlm = DiffusionLM(build_model(cfg))
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = ShardingRules(cfg, mesh)
    aparams = dlm.init_abstract()
    rep = lambda t: jax.tree.map(lambda _: rules.replicated(), t)
    psh = {
        "backbone": rules.param_sharding(aparams["backbone"]),
        "time_mlp": rep(aparams["time_mlp"]),
        "in_proj": rep(aparams["in_proj"]),
        "eps_head": rep(aparams["eps_head"]),
    }
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.float32)
    xsh = NamedSharding(mesh, P(data_axes(mesh), None, None))
    if solver == "era":
        sc = ERAConfig(
            nfe=nfe, k=4,
            solver_dtype=jnp.bfloat16 if bf16_buffer else jnp.float32,
        )
    else:
        sc = SolverConfig(nfe=nfe)
    program = sample_program(dlm, linear_schedule(), solver, sc)

    rec = {
        "arch": arch, "mesh": mesh_kind, "entry": f"sample_{solver}",
        "solver": solver, "nfe": nfe, "batch": batch, "seq": seq,
        "bf16_buffer": bf16_buffer, "num_devices": mesh.devices.size,
        "ok": False,
    }
    t0 = time.perf_counter()
    with mesh, activation_sharding(data_axes(mesh)):
        compiled = (
            jax.jit(program, in_shardings=(psh, xsh))
            .lower(aparams, x)
            .compile()
        )
    rec["compile_s"] = round(time.perf_counter() - t0, 2)
    rec["hlo"] = hlo_analysis.analyze(compiled.as_text())
    ma = compiled.memory_analysis()
    rec["memory_analysis"] = {
        "argument_size_in_bytes": int(ma.argument_size_in_bytes),
        "temp_size_in_bytes": int(ma.temp_size_in_bytes),
    }
    rec["ok"] = True
    os.makedirs(out_dir, exist_ok=True)
    suffix = "bf16" if bf16_buffer else "f32"
    path = os.path.join(out_dir, f"solver__{arch}__{solver}_{suffix}__{mesh_kind}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--out", default=os.path.normpath(OUT_DIR))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument(
        "--solver-program", action="store_true",
        help="lower the full ERA/DDIM sampling loop instead of an input shape",
    )
    ap.add_argument("--solver", default="era")
    ap.add_argument("--nfe", type=int, default=10)
    ap.add_argument("--bf16-buffer", action="store_true")
    args = ap.parse_args()

    if args.all:
        run_all(args.out, resume=not args.no_resume)
        return
    if args.solver_program:
        rec = run_solver_program(
            args.arch or "qwen2-1.5b", args.mesh, args.out,
            solver=args.solver, nfe=args.nfe, bf16_buffer=args.bf16_buffer,
        )
        print(json.dumps(rec, indent=1))
        return
    assert args.arch and args.shape, "--arch and --shape required"
    rec = run_one(args.arch, args.shape, args.mesh, args.out)
    drop = {"cost_analysis"}
    print(json.dumps({k: v for k, v in rec.items() if k not in drop}, indent=1))


if __name__ == "__main__":
    main()
