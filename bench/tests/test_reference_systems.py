"""The reference judges any pipeline the program serves: per-stage stage
constants, hard per-stage memory caps and the decoder's profile term.

* A uniform system, given as scalars or as per-stage lists, reads exactly
  what the reference read before it took per-stage systems (a digest of
  its outputs, pinned then).
* On a heterogeneous and a memory-capped 4-stage chain, what the program
  serves passes :func:`bench.runners.serve.compare` within the
  configurations' limits, and the reference's schedule equals the
  program's host ``repair(exact_dp(order))``.
* A served schedule that breaks a cap fails the comparison.  The
  reference without the profile term departs from a profile-conditioned
  decode's per-step log-probabilities; ``compare`` alone cannot see that,
  since the term moves no greedy pick of the release.
"""

import dataclasses
import hashlib
import json
import sys
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.graphs import synth_dag, table1_variants  # noqa: E402
from bench.lib import reference as ref  # noqa: E402
from bench.lib.graphspec import to_program  # noqa: E402
from bench.runners.serve import compare  # noqa: E402

CONFIGS = ("coral-table1-k4", "coral-synth30-k4")
STAGE_KEYS = ("compute_rate", "compute_eff", "link_bw", "cache_bytes")

#: sha256 of :func:`_digest` on either configuration's system, taken from
#: the reference as it stood before it read per-stage systems
PINNED = "f0a66c97a21596dcccf7a58ea09ab9af452abfb75dba270400416e81063c4d81"


def _config(name: str) -> dict:
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def _topo_order(spec, rng) -> np.ndarray:
    """A topological order of ``spec`` drawn from ``rng``."""
    left = np.array([len(ps) for ps in spec.parents])
    children = spec.children()
    ready = [v for v in range(spec.n) if left[v] == 0]
    out = []
    while ready:
        v = ready.pop(int(rng.integers(len(ready))))
        out.append(v)
        for c in children[v]:
            left[c] -= 1
            if left[c] == 0:
                ready.append(c)
    return np.asarray(out, np.int64)


def _digest_cases():
    rng = np.random.default_rng([16, 0])
    specs = synth_dag.make(rng, 6, {"n": 30, "degs": [2, 3, 4, 5, 6]})
    specs += [table1_variants.variant(m, rng)
              for m in ("Xception", "DenseNet121")]
    cases = []
    for s in specs:
        cases.append((s, np.arange(s.n)))
        cases.append((s, _topo_order(s, rng)))
    return cases


def _digest(system: dict) -> str:
    """rho, schedule and objective of the served-order kind, and rho with
    the bfloat16 cost table, over seeded synthetic and Table-I graphs."""
    h = hashlib.sha256()
    for spec, order in _digest_cases():
        for dtype in (None, ml_dtypes.bfloat16):
            a = ref.rho(spec, order, system, dtype)
            s = ref.schedule(spec, order, system, dtype)
            h.update(np.asarray(a, np.int64).tobytes())
            h.update(np.asarray(s, np.int64).tobytes())
            h.update(np.asarray(ref.objective(spec, s, system),
                                np.float64).tobytes())
    return h.hexdigest()


def _as_lists(system: dict) -> dict:
    k = int(system["n_stages"])
    return {key: ([v] * k if key in STAGE_KEYS else v)
            for key, v in system.items()}


@pytest.mark.parametrize("form", ["scalars", "lists"])
@pytest.mark.parametrize("config", CONFIGS)
def test_uniform_system_reads_as_before(config, form):
    system = _config(config)["system"]
    if form == "lists":
        system = _as_lists(system)
    assert _digest(system) == PINNED


# --------------------------------------------------------------------- #
# heterogeneous and capped chains, served by the program
# --------------------------------------------------------------------- #
LIMITS = _config("coral-table1-k4")["correct"]
HETERO_SEED = 16
#: per-stage budget as a share of the largest graph's parameter bytes:
#: 0.6 binds on the synthetic graphs; on the Table-I variants no stage
#: of a 4-stage split comes near 0.6, so they are held to 0.4
CAP_SHARE = {"synth": 0.6, "table1": 0.4}


def _specs(family: str):
    rng = np.random.default_rng([16, 1])
    if family == "synth":
        return synth_dag.make(rng, 16, {"n": 30, "degs": [2, 3, 4, 5, 6]})
    return [table1_variants.variant(m, rng)
            for m in ("Xception", "Xception", "ResNet50")]


def _system(kind: str, specs) -> dict:
    """The seeded heterogeneous chain as a configuration's dict, with a
    memory budget for ``capped``."""
    from repro.eval.scenarios import hetero_system
    sysd = {k: (list(v) if isinstance(v, tuple) else v) for k, v in
            dataclasses.asdict(hetero_system(4, HETERO_SEED)).items()}
    if kind == "capped":
        family = "synth" if specs[0].n == 30 else "table1"
        total = max(float(s.param_bytes.sum()) for s in specs)
        sysd["mem_capacity"] = CAP_SHARE[family] * total
    return sysd


@pytest.fixture(scope="module")
def release():
    from repro.core import RespectScheduler
    path = ROOT / _config("coral-table1-k4")["release"]
    return RespectScheduler.from_release(path), ref.load_params(path)


def _serve(sched, specs, sysd):
    from repro.core import PipelineSystem
    res = sched.schedule_many([to_program(s) for s in specs], 4,
                              PipelineSystem(**sysd), use_cache=False)
    return ([np.asarray(r["order"]) for r in res],
            [np.asarray(r["assignment"]) for r in res])


@pytest.mark.parametrize("family", ["synth", "table1"])
@pytest.mark.parametrize("kind", ["hetero", "capped"])
def test_served_pipeline_passes_compare(release, kind, family):
    from repro.core import PipelineSystem
    from repro.core.exact import exact_dp
    from repro.core.postprocess import repair
    sched, params = release
    specs = _specs(family)
    sysd = _system(kind, specs)
    orders, assigns = _serve(sched, specs, sysd)
    numbers = compare(params, specs, orders, assigns, sysd)
    assert numbers["logit_gap_max"] <= LIMITS["logit_gap_max"], numbers
    assert numbers["invalid_schedules"] == 0, numbers
    assert numbers["bottleneck_excess"] <= LIMITS["bottleneck_excess"], numbers

    system = PipelineSystem(**sysd)
    uncapped = _system("hetero", specs)
    binds = 0
    for s, o, a in zip(specs, orders, assigns):
        g = to_program(s)
        host = repair(g, exact_dp(g, 4, system, order=o)[0], 4,
                      mem_capacity=system.capacity_vector())
        ours = ref.schedule(s, o, sysd)
        np.testing.assert_array_equal(ours, host)
        assert ref.objective(s, a, sysd)[0] < ref.CAPACITY_PENALTY_S
        binds += not np.array_equal(ours, ref.schedule(s, o, uncapped))
    if kind == "capped":
        assert binds > 0       # the budget moves some schedule


def _overfill(spec, assign, caps):
    """``assign`` with one node moved, dependencies kept, onto a stage that
    its parameters take over the budget; None where no node can be."""
    loads = np.zeros(len(caps))
    np.add.at(loads, assign, spec.param_bytes)
    children = spec.children()
    for v in np.argsort(-spec.param_bytes):
        lo = max((assign[u] for u in spec.parents[v]), default=0)
        hi = min((assign[c] for c in children[v]), default=len(caps) - 1)
        for t in range(lo, hi + 1):
            if t != assign[v] and loads[t] + spec.param_bytes[v] > caps[t]:
                out = assign.copy()
                out[v] = t
                return out
    return None


@pytest.mark.parametrize("family", ["synth", "table1"])
def test_schedule_over_its_cap_fails_compare(release, family):
    sched, params = release
    specs = _specs(family)
    sysd = _system("capped", specs)
    orders, assigns = _serve(sched, specs, sysd)
    caps = ref.capacities(sysd)
    broken = next(filter(lambda x: x[1] is not None, (
        (j, _overfill(s, a, caps)) for j, (s, a) in
        enumerate(zip(specs, assigns)))))
    j, a = broken
    assert ref.valid_schedule(specs[j], a, 4)
    numbers = compare(params, [specs[j]], [orders[j]], [a], sysd)
    assert numbers["invalid_schedules"] == 0
    assert numbers["bottleneck_excess"] > LIMITS["bottleneck_excess"]


# --------------------------------------------------------------------- #
# the profile term
# --------------------------------------------------------------------- #
def test_profile_matches_the_program():
    from repro.core import PipelineSystem
    specs = _specs("synth")
    for sysd in (_config("coral-synth30-k4")["system"],
                 _as_lists(_config("coral-synth30-k4")["system"]),
                 _system("hetero", specs), _system("capped", specs)):
        np.testing.assert_array_equal(
            ref.profile(sysd), PipelineSystem(**sysd).profile_features())
    assert not ref.profile(_config("coral-synth30-k4")["system"]).any()


def _reference_logp(params, specs, orders, sysd) -> list[np.ndarray]:
    """Per graph the reference's log-probability of each served node:
    the served logit less the log-sum-exp of every node's logit at that
    step, read one probe column at a time (a masked node reads -inf)."""
    n = max(s.n for s in specs)
    cols = [ref.pointer_readings(
        params, specs, orders, probes=[np.full(s.n, min(j, s.n - 1))
                                       for s in specs], system=sysd)
        for j in range(n)]
    out = []
    for i, s in enumerate(specs):
        logits = np.stack([cols[j][i]["probe"] for j in range(s.n)])
        top = logits.max(axis=0)
        lse = top + np.log(np.exp(logits - top).sum(axis=0))
        out.append(cols[0][i]["served"] - lse)
    return out


def test_profile_term_follows_the_program(release):
    """A release with a seeded ``w_sys``: the program's conditioned scan
    decode reads within the limit under the reference, and its per-step
    log-probabilities agree with the reference's only with the term."""
    import jax
    import jax.numpy as jnp
    from repro.core import PipelineSystem, RespectScheduler, ptrnet
    from repro.core.embedding import embed_graph
    sched, params = release
    w_sys = np.random.default_rng(HETERO_SEED).normal(
        size=(ref.SYS_FEAT_DIM, params["dec0"].shape[0])).astype(np.float32)
    with_term = dict(params, w_sys=w_sys)
    program = RespectScheduler(dict(sched.params, w_sys=jnp.asarray(w_sys)))
    specs = synth_dag.make(np.random.default_rng([16, 2]), 16,
                           {"n": 30, "degs": [2, 3, 4, 5, 6]})
    sysd = _system("hetero", specs)
    orders, assigns = _serve(program, specs, sysd)
    numbers = compare(with_term, specs, orders, assigns, sysd)
    assert numbers["logit_gap_max"] <= LIMITS["logit_gap_max"], numbers
    assert numbers["invalid_schedules"] == 0

    prof = jnp.asarray(PipelineSystem(**sysd).profile_features())
    decode = jax.jit(lambda p, f, m: ptrnet.greedy_order(p, f, m,
                                                         sys_feat=prof))
    logp = []
    for s, o in zip(specs, orders):
        g = to_program(s)
        got, lp, _ = decode(program.params, jnp.asarray(embed_graph(g)),
                            jnp.asarray(g.parent_matrix(6)))
        np.testing.assert_array_equal(np.asarray(got), o)
        logp.append(np.asarray(lp))

    def departure(p):
        return max(float(np.max(np.abs(r - lp)))
                   for r, lp in zip(_reference_logp(p, specs, orders, sysd),
                                    logp))

    assert departure(with_term) <= LIMITS["logit_gap_max"]
    assert departure(params) > LIMITS["logit_gap_max"]
