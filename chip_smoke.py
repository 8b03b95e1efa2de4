"""Bring-up check of the served ERA sampling path on a TPU chip.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --chips 4     # the 4-chip mesh path and its 1-chip twin
    python chip_smoke.py --rehearse    # CPU rehearsal at the smoke preset

One chip: builds qwen2-1.5b as a ``DiffusionLM`` at its published widths and
full depth (28 layers, d=1536, 12/2 heads, hd=128) with seeded random
weights, boots the engine through ``build_engine`` + ``serve_frontdoor`` on
loopback, waits for ``/readyz``, and sends requests over
``FrontDoorClient`` for both solvers, at batch 1 and batch 3-8, with seq
lengths below the 512 bucket so the masked flash kernel and the padded ERA
step both run.  It then checks that

* every ``x0`` has the requested shape and is finite;
* a wire result is bitwise equal to the same request flushed in-process by
  the same engine (same compiled shape);
* one ERA request agrees with a float32 reference of the same sampler
  (``highest`` matmul precision, naive XLA attention, the jnp ERA combine)
  within a tolerance calibrated in the same run (see ``check_reference``);
* the compiled program holds both Mosaic kernels (``tpu_custom_call``) and
  no SDPA fallback fired.

It also prints, without a bound, how far one request moves between batch
buckets 1 and 8, in bf16 and in float32 (``bucket_gap``).

``--chips 4`` runs only the mesh path: one batch of 8 drained by a
``make_sampler_mesh(4)`` engine against the same batch drained by a one-chip
engine on device 0, printing where each shard lives.

Without ``--rehearse`` the script fails before any work unless JAX's first
device is a TPU.  ``--rehearse`` forces the CPU backend (kernels in
interpret mode) and the 2-layer smoke preset; its last line never names a
TPU.  Every failed phase raises, so the script exits non-zero and prints no
result line; on success the last line of stdout is one JSON object naming
the device.

The persistent compilation cache lives where ``repro.serving.cache_dir``
says: ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` here.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

NFE = 10
ERA_K = 4
BATCH_BUCKETS = (1, 8)

#: gain of the randomly drawn eps head.  A random denoiser makes the
#: sampling ODE chaotic unless its head is small: at full qwen2-1.5b size on
#: a v5e chip, a 1e-6 relative change of x_T moves ERA's x0 (10 NFE) by
#: 2.4e-3 at gain 0.03 but by 1.1e-5 at 0.01, while the backbone still moves
#: x0 by 3% between bf16 and float32 compute at 0.01.  (At unit gain, on the
#: 2-layer smoke preset, the same change already moves x0 by 5%.)
EPS_HEAD_GAIN = 0.01


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rehearse",
        action="store_true",
        help="CPU rehearsal at the 2-layer smoke preset (never a chip result)",
    )
    return ap.parse_args(argv)


@contextlib.contextmanager
def phase(name: str):
    print(f"[{name}] ...", flush=True)
    t0 = time.perf_counter()
    yield
    print(f"[{name}] ok in {time.perf_counter() - t0:.2f} s", flush=True)


def check(cond: bool, message: str) -> None:
    if not cond:
        raise AssertionError(message)


def relerr(a, b) -> float:
    """||a - b|| / ||b|| in float64."""
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def build_model(seed: int, rehearse: bool):
    """qwen2-1.5b as a DiffusionLM with every parameter drawn from ``seed``.

    The library zero-initialises the eps head, which makes eps == x_t
    whatever the backbone computes; the head is drawn here instead (at
    ``EPS_HEAD_GAIN``), so the backbone, and so attention and its mask,
    shapes every result."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model as build_backbone
    from repro.models.diffusion import DiffusionLM

    cfg = get_config("qwen2-1.5b")
    if rehearse:
        # the smoke preset computes in float32; keep the chip's bf16
        cfg = cfg.smoke().with_(dtype=cfg.dtype)
    dlm = DiffusionLM(build_backbone(cfg))
    k_init, k_w, k_b = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = jax.jit(dlm.init)(k_init)  # one program, not one per leaf
    d = cfg.d_model
    w = jax.random.normal(k_w, (d, d), cfg.param_dtype) * (EPS_HEAD_GAIN / d**0.5)
    b = jax.random.normal(k_b, (d,), cfg.param_dtype) * EPS_HEAD_GAIN
    params["eps_head"] = {"w": w, "b": b}
    n = sum(x.size for x in jax.tree.leaves(params))
    print(
        f"model {cfg.name}: layers={cfg.num_layers} d={d} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} hd={cfg.resolved_head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} params={n} "
        f"compute={jax.numpy.dtype(cfg.dtype).name}",
        flush=True,
    )
    return dlm, params


def kernel_report(compiled_text: str) -> dict:
    """Mosaic kernels in one compiled program, by the pallas_call's name in
    the op's scope (a vmapped kernel sits in a loop under that scope)."""
    scopes = [
        re.search(r'op_name="([^"]*)"', line)
        for line in compiled_text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    names = [m.group(1).split("/") if m else [] for m in scopes]
    return {
        "tpu_custom_call": len(scopes),
        "flash_attention": sum("flash_attention" in n for n in names),
        "era_update": sum("era_update" in n for n in names),
    }


def peak_bytes(device) -> str:
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return str(stats["peak_bytes_in_use"])


#: float32 rounding, as an input perturbation: x_T scaled by (1 + 1e-6)
PERTURBATION = 1e-6
#: two float32 paths may differ by this many times the sampler's own
#: response to PERTURBATION (see rounding_bound)
ROUNDING_FACTOR = 10.0


def float32_twin(dlm):
    """The same denoiser computing in float32 (same params, same kernels)."""
    import jax.numpy as jnp

    from repro.models import build_model as build_backbone
    from repro.models.diffusion import DiffusionLM

    return DiffusionLM(build_backbone(dlm.config.with_(dtype=jnp.float32)))


def reference_sampler(dlm, schedule):
    """The float32 reference of the served ERA sampler: naive XLA
    attention and the jnp ERA combine, run at a request's exact shape
    (call it under ``highest`` matmul precision)."""
    import jax
    import jax.numpy as jnp

    from repro.core import ERAConfig, get_solver
    from repro.models import build_model as build_backbone
    from repro.models.diffusion import DiffusionLM

    ref = DiffusionLM(build_backbone(
        dlm.config.with_(dtype=jnp.float32, attention_impl="naive")
    ))
    era_ref = ERAConfig(nfe=NFE, k=ERA_K, per_sample=True, use_fused_update=False)
    return jax.jit(
        lambda p, xi: get_solver("era")(ref.eps_fn(p), xi, schedule, era_ref).x0
    )


def request_noise(req, d: int):
    """x_T exactly as the engine draws it for ``req``."""
    import jax
    import jax.numpy as jnp

    shape = (req.batch, req.seq_len, d)
    return jax.random.normal(jax.random.PRNGKey(req.seed), shape, jnp.float32)


def rounding_bound(reference, params, x, ref_x0) -> float:
    """How far two float32 paths may drift apart on this sampler.

    A sampler with random weights amplifies rounding by orders of
    magnitude more at 28 layers than at 2 (the smoke preset), so no fixed
    bound fits both.  The run measures it instead: the reference's response
    to x_T scaled by ``1 + PERTURBATION``, times ``ROUNDING_FACTOR`` for the
    many operations whose rounding adds up (measured on a v5e chip at
    ``EPS_HEAD_GAIN``: the float32 path through the kernels lands at 0.7
    times the response)."""
    import numpy as np

    nudged = np.asarray(reference(params, x * (1.0 + PERTURBATION)))
    bound = ROUNDING_FACTOR * relerr(nudged, ref_x0)
    check(bound > 0.0, "the reference ignores its input")
    return bound


def run_now(engine, params, reqs):
    """Queue ``reqs`` on a scheduler over ``engine``, flush them on this
    thread, and return their results in order."""
    from repro.serving import AsyncBatchedSampler

    queue = AsyncBatchedSampler(engine, params)
    futs = [queue.submit(r) for r in reqs]
    queue.flush()
    return [f.result() for f in futs]


def bucket_gap(label, engine, params, solo, mate) -> None:
    """Print how far one request moves between batch buckets: run alone
    (bucket 1), then fused with ``mate`` into bucket 8.  docs/serving.md
    promises ``atol=1e-6`` across batch buckets; this measures it."""
    import numpy as np

    (a,) = run_now(engine, params, [solo])
    b, _ = run_now(engine, params, [solo, mate])
    check(
        (a.padded_batch, b.padded_batch) == (1, 8),
        f"ran at batch buckets {a.padded_batch}, {b.padded_batch}",
    )
    x1, x8 = np.asarray(a.x0), np.asarray(b.x0)
    print(
        f"batch bucket 1 vs 8 ({label}): max_abs={float(np.max(np.abs(x1 - x8)))!r} "
        f"rel={relerr(x8, x1)!r} bitwise={np.array_equal(x1, x8)}",
        flush=True,
    )


def check_reference(dlm, params, schedule, cfg, req, served_bf16, seq_bucket, solo):
    """Hold one request to a float32 reference of the same sampler.

    The request runs again through the served path in a float32-compute twin
    of the served engine (``build_engine`` with the same config: the Pallas
    kernels, the padded batch-bucket x seq-bucket program), and through
    :func:`reference_sampler` at its exact shape.  The two differ only in
    float32 summation order, so they must agree within
    :func:`rounding_bound`.  Two controls show in the same run that the
    bound has power: the bf16 served result (what a bf16 slip on either side
    gives) and the reference on the request right-padded to the seq bucket
    with the pad keys visible (a dropped mask) must both miss the reference
    by more than it.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import build_engine

    with jax.default_matmul_precision("highest"):
        twin = build_engine(
            float32_twin(dlm), schedule, dataclasses.replace(cfg, warmup="none")
        )
        served32 = np.asarray(run_now(twin, params, [req])[0].x0)
        bucket_gap("float32", twin, params, solo, req)

        reference = reference_sampler(dlm, schedule)
        x = request_noise(req, dlm.config.d_model)
        ref32 = np.asarray(reference(params, x))
        tol = rounding_bound(reference, params, x, ref32)
        pad = jnp.zeros(
            (req.batch, seq_bucket - req.seq_len, dlm.config.d_model), jnp.float32
        )
        nomask = np.asarray(reference(params, jnp.concatenate([x, pad], axis=1)))
        nomask = nomask[:, : req.seq_len]

    e_served = relerr(served32, ref32)
    e_bf16 = relerr(served_bf16, ref32)
    e_nomask = relerr(nomask, ref32)
    print(
        f"fp32 reference rel_err: served_f32={e_served!r} (tol {tol!r}) "
        f"controls: served_bf16={e_bf16!r} dropped_mask={e_nomask!r}",
        flush=True,
    )
    check(e_served <= tol, f"served vs fp32 reference {e_served} > {tol}")
    check(e_bf16 > tol, f"tolerance {tol} cannot see bf16 ({e_bf16})")
    check(e_nomask > tol, f"tolerance {tol} cannot see a dropped mask ({e_nomask})")


def one_chip(args, device) -> None:
    import jax
    import numpy as np

    from repro.core import linear_schedule
    from repro.kernels import ops
    from repro.models import attention as attn
    from repro.serving import (
        EngineConfig,
        FrontDoorClient,
        SampleRequest,
        build_engine,
        serve_frontdoor,
        warmup_kwargs,
    )

    seq_bucket = 128 if args.rehearse else 512
    schedule = linear_schedule()
    with phase("model"):
        dlm, params = build_model(args.seed, args.rehearse)
    impl = attn.resolve_impl(dlm.config, seq_bucket, seq_bucket)
    era_step = "interpret" if ops.interpret_mode() else "compiled (Mosaic)"
    print(f"attention impl: {impl}; ERA step: Pallas era_update, {era_step}")
    if not args.rehearse:
        check(impl == "pallas", f"attention resolves to {impl!r} on TPU")
        check(not ops.interpret_mode(), "Pallas kernels in interpret mode")

    fallbacks: list[tuple[str, str]] = []
    attn.register_fallback_observer(lambda i, r: fallbacks.append((i, r)))

    cfg = EngineConfig(
        solver="era",
        nfe=NFE,
        k=ERA_K,
        per_sample=True,
        batch_buckets=BATCH_BUCKETS,
        seq_buckets=(seq_bucket,),
        warmup="grid",
        compile_cache=True,
    )
    engine = build_engine(dlm, schedule, cfg)
    warm = {**warmup_kwargs(cfg), "solvers": ("era", "ddim")}
    door = serve_frontdoor(engine, params, warmup=warm)
    try:
        client = FrontDoorClient(door.url)
        with phase("readyz"):
            deadline = time.monotonic() + 1000
            while True:
                ready = client.readyz()
                if ready["ready"]:
                    break
                check("error" not in ready, f"warmup failed: {ready.get('error')}")
                check(time.monotonic() < deadline, "warmup did not finish")
                time.sleep(1.0)
            w = ready["warmup"]
            print(
                f"warmup: programs={w['total']} compile_s={w['wall_s']!r} "
                f"fresh={w['fresh']} disk={w['disk']}",
                flush=True,
            )
            check(w["total"] == 2 * len(BATCH_BUCKETS), f"warmup grid {w}")

        short = seq_bucket * 3 // 4
        reqs = [
            SampleRequest(batch=3, seq_len=short, nfe=NFE, solver="era", seed=11),
            SampleRequest(batch=1, seq_len=seq_bucket, nfe=NFE, solver="era", seed=12),
            SampleRequest(batch=8, seq_len=seq_bucket * 2 // 5, nfe=NFE,
                          solver="ddim", seed=13),
            SampleRequest(batch=1, seq_len=short, nfe=NFE, solver="ddim", seed=14),
        ]
        wire = {}
        with phase("wire requests"):
            for req in reqs:
                t0 = time.perf_counter()
                res = client.sample(req)
                x0 = np.asarray(res.x0)
                print(
                    f"  {req.solver} batch={req.batch} seq_len={req.seq_len}: "
                    f"x0{x0.shape} padded=({res.padded_batch}, "
                    f"{res.padded_seq_len}) wall_s={time.perf_counter() - t0!r}",
                    flush=True,
                )
                check(
                    x0.shape == (req.batch, req.seq_len, dlm.config.d_model),
                    f"x0 shape {x0.shape} for {req}",
                )
                check(bool(np.all(np.isfinite(x0))), f"non-finite x0 for {req}")
                wire[req] = x0

        with phase("wire == in-process"):
            req = reqs[0]
            local = np.asarray(run_now(engine, params, [req])[0].x0)
            check(np.array_equal(local, wire[req]), "wire x0 != in-process x0")

        with phase("batch buckets"):
            bucket_gap("bf16", engine, params, reqs[1], reqs[0])

        with phase("compiled kernels"):
            key = next(
                k for k in engine.compile_cache()
                if k[0] == "era" and k[2] == max(BATCH_BUCKETS)
            )
            report = kernel_report(engine.compile_cache()[key].as_text())
            print(f"era program batch={key[2]} seq={key[3]}: {report}", flush=True)
            if not args.rehearse:
                check(report["flash_attention"] >= 1, "no Mosaic flash kernel")
                check(report["era_update"] >= 1, "no Mosaic ERA kernel")
            check(not fallbacks, f"SDPA fallbacks fired: {fallbacks}")
            metrics = client.metrics()
            fired = [
                line for line in metrics.splitlines()
                if line.startswith("sampler_masked_fallback_total{")
                and float(line.rsplit(" ", 1)[1]) > 0
            ]
            check(not fired, f"fallback counter moved: {fired}")
    finally:
        door.stop()

    with phase("fp32 reference"):
        check_reference(
            dlm, params, schedule, cfg, reqs[0], wire[reqs[0]], seq_bucket,
            reqs[1],
        )
    print(f"peak_bytes_in_use: {peak_bytes(device)}", flush=True)


def four_chips(args, devices) -> None:
    """The mesh path against one chip, on the same batch of 8 rows.

    The mesh program and the one-chip program are different compiled
    programs, so bf16 rounding lands differently in them (on four v5e
    chips the bf16 drains are not bitwise equal), and the sampler amplifies
    that.  Two checks hold the mesh to the same math: the float32 twins of
    both engines (``highest`` precision) must agree within
    :func:`rounding_bound`, and the bf16 mesh result may sit at most twice
    as far from the one-chip float32 result as the one-chip bf16 result
    does.  A row placed on the wrong chip, a lost mask or a kernel fed
    another shard's rows moves x0 by far more than either allows."""
    import jax
    import numpy as np

    from repro.core import linear_schedule
    from repro.launch.mesh import make_sampler_mesh
    from repro.parallel.sharding import ParamReplicator
    from repro.serving import EngineConfig, SampleRequest, build_engine

    seq_bucket = 128 if args.rehearse else 512
    schedule = linear_schedule()
    with phase("model"):
        dlm, params = build_model(args.seed, args.rehearse)
    twin = float32_twin(dlm)
    cfg = EngineConfig(
        solver="era", nfe=NFE, k=ERA_K, per_sample=True,
        batch_buckets=(8,), seq_buckets=(seq_bucket,), compile_cache=True,
    )
    reqs = [
        SampleRequest(batch=3, seq_len=seq_bucket * 3 // 4, nfe=NFE, seed=21),
        SampleRequest(batch=5, seq_len=seq_bucket, nfe=NFE, seed=22),
    ]

    def run(denoiser, p, mesh=None):
        engine = build_engine(denoiser, schedule, cfg, mesh=mesh)
        out = run_now(engine, p, reqs)
        check(out[0].padded_batch == 8, f"batch ran at {out[0].padded_batch}")
        return engine, [np.asarray(r.x0) for r in out]

    with phase("one chip (device 0)"):
        _, single16 = run(dlm, params)
        with jax.default_matmul_precision("highest"):
            _, single32 = run(twin, params)
            reference = reference_sampler(dlm, schedule)
            x = request_noise(reqs[0], dlm.config.d_model)
            tol = rounding_bound(reference, params, x, np.asarray(reference(params, x)))
    with phase("mesh of 4"):
        mesh = make_sampler_mesh(4)
        # one replicated copy: the device-0 original is dropped so the
        # chip holds the weights once
        params = ParamReplicator(mesh)(params)
        leaf = params["eps_head"]["w"]
        print(
            "params eps_head.w on devices "
            f"{sorted(s.device.id for s in leaf.addressable_shards)}",
            flush=True,
        )
        meshed, mesh16 = run(dlm, params, mesh)
        with jax.default_matmul_precision("highest"):
            _, mesh32 = run(twin, params, mesh)
        (compiled,) = meshed.compile_cache().values()
        x_sharding = compiled.input_shardings[0][1]
        shape = (8, seq_bucket, dlm.config.d_model)
        rows = {}
        for dev, idx in x_sharding.devices_indices_map(shape).items():
            rows[dev.id] = (idx[0].start, idx[0].stop)
        print(f"latent rows per device: {dict(sorted(rows.items()))}", flush=True)
        check(len(set(rows.values())) == 4, f"rows not spread over 4 chips: {rows}")
    with phase("mesh == one chip"):
        for r, a16, b16, a32, b32 in zip(reqs, mesh16, single16, mesh32, single32):
            check(a16.shape == b16.shape, f"{r}: {a16.shape} vs {b16.shape}")
            check(bool(np.all(np.isfinite(a16))), f"non-finite x0 for {r}")
            e32 = relerr(a32, b32)
            mesh16 = relerr(a16, b32)
            single16 = relerr(b16, b32)
            print(
                f"  batch={r.batch} seq_len={r.seq_len}: f32 mesh vs one chip "
                f"{e32!r} (tol {tol!r}); bf16 vs one-chip f32: mesh "
                f"{mesh16!r}, one chip {single16!r}; bf16 mesh vs one chip "
                f"{relerr(a16, b16)!r}, bitwise={np.array_equal(a16, b16)}",
                flush=True,
            )
            check(e32 <= tol, f"f32 mesh drain differs by {e32} > {tol}")
            check(mesh16 <= 2.0 * single16, f"bf16 mesh drain is off by {mesh16}")
    for d in devices[:4]:
        print(f"peak_bytes_in_use device {d.id}: {peak_bytes(d)}", flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(
        f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}",
        flush=True,
    )
    want = "cpu" if args.rehearse else "tpu"
    if dev.platform != want:
        print(f"no {want.upper()} found (platform {dev.platform})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if args.chips == 4:
        four_chips(args, devices)
    else:
        one_chip(args, dev)
    line = {
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }
    if args.rehearse:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
