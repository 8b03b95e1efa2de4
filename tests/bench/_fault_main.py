"""Run one cell at the smoke sizes on the CPU with a fault planted in the
program's timed path, and print the result line (as ``bench/run.py`` does).

    python tests/bench/_fault_main.py <fault> <cell> <seed>

Faults: ``none``; ``frozen_step`` (every solver step returns its latents
unchanged); ``half_batch`` and ``half_batch_tail`` (the denoiser runs on
the first, or the second, half of each fused batch only, the other rows
get eps = x_t); ``altered_answer`` (each answer's last row is negated
where the executor produces it).
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def plant(fault: str) -> None:
    if fault == "none":
        return
    if fault == "frozen_step":
        from repro.core import era
        from repro.kernels import ops

        era.ddim_step = lambda schedule, x, eps, t_cur, t_next: x
        fused = ops.era_step
        ops.era_step = lambda x, *a, **kw: (x, fused(x, *a, **kw)[1])
        return
    if fault in ("half_batch", "half_batch_tail"):
        from repro.models.diffusion import DiffusionLM

        eps = DiffusionLM.eps

        def half(self, params, x_t, t, lengths=None):
            import jax.numpy as jnp

            out = eps(self, params, x_t, t, lengths=lengths)
            head = jnp.arange(x_t.shape[0]) < max(1, x_t.shape[0] // 2)
            keep = ~head if fault == "half_batch_tail" else head
            return jnp.where(keep[:, None, None], out, x_t)

        DiffusionLM.eps = half
        return
    if fault == "altered_answer":
        from repro.serving import executor

        run = executor.FusedExecutor._run_chunk_locked

        def altered(self, params, seq_len, nfe, chunk, results, pad):
            run(self, params, seq_len, nfe, chunk, results, pad)
            for ticket, _, _ in chunk:
                r = results[ticket]
                r.x0 = r.x0.at[-1].multiply(-1.0)

        executor.FusedExecutor._run_chunk_locked = altered
        return
    raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    fault, cell, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
    plant(fault)
    from bench import harness

    line = harness.run_cell(cell, seed, 3.0, False, rehearse=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
