"""Info-dict key audit: the documented constants in
`repro.serving.result_keys` are the ONLY spelling of the engine's telemetry
and stats keys.

The grep wall scans every serving-layer and benchmark source file for
quoted literals of the documented keys — a stringly-typed duplicate
(`info["wall_s"]` instead of `info[K.WALL_S]`) is a latent rename hazard
and fails here by filename:line.
"""

import re
from pathlib import Path

from repro.core import ERAConfig, get_solver, linear_schedule

from conftest import AnalyticGaussian, OracleDenoiser, run_flush
from repro.serving import FusedExecutor, SampleRequest, result_keys as K

ROOT = Path(__file__).resolve().parent.parent
SCANNED_DIRS = ("src/repro/serving", "benchmarks")
# the one place the literals are allowed to exist
DEFINING_FILE = ROOT / "src/repro/serving/result_keys.py"

_WALL = re.compile(
    r"""["'](%s)["']""" % "|".join(sorted(K.INFO_KEYS + K.STATS_KEYS
                                          + K.AUX_KEYS, key=len, reverse=True))
)


def test_no_stringly_typed_key_duplicates():
    offenders = []
    for d in SCANNED_DIRS:
        for path in sorted((ROOT / d).rglob("*.py")):
            if path == DEFINING_FILE:
                continue
            for n, line in enumerate(
                path.read_text().splitlines(), start=1
            ):
                m = _WALL.search(line)
                if m:
                    offenders.append(
                        f"{path.relative_to(ROOT)}:{n}: "
                        f"stringly-typed key {m.group(0)} — use "
                        f"result_keys.{m.group(1).upper()}"
                    )
    assert not offenders, "\n".join(offenders)


def test_constants_cover_info_dict():
    """Every key a real SampleResult.info exposes is documented: INFO_KEYS
    for the engine telemetry, AUX_KEYS for the solver diagnostics — no
    undocumented key can appear without failing here."""
    analytic = AnalyticGaussian()
    engine = FusedExecutor(
        OracleDenoiser(analytic),
        linear_schedule(),
        solver_config=ERAConfig(nfe=6, k=3, per_sample=True),
        batch_buckets=None,
    )
    (res,) = run_flush(engine, [SampleRequest(batch=1, seq_len=4, nfe=6)])
    info = res.info
    documented = set(K.INFO_KEYS) | set(K.AUX_KEYS)
    assert set(K.INFO_KEYS) <= set(info)
    undocumented = set(info) - documented
    assert not undocumented, (
        f"SampleResult.info exposes undocumented keys {sorted(undocumented)} "
        f"— add them to repro.serving.result_keys"
    )


def test_aux_keys_match_solver_output():
    """The documented AUX_KEYS spellings are the ones the solver actually
    emits (guards against constants drifting from core)."""
    analytic = AnalyticGaussian()
    import jax

    out = get_solver("era")(
        analytic.eps,
        jax.random.normal(jax.random.PRNGKey(0), (2, 4)),
        analytic.schedule,
        ERAConfig(nfe=6, k=3, per_sample=True),
    )
    assert K.DELTA_EPS_HISTORY in out.aux
    assert K.ERS_SELECTION_HISTORY in out.aux
