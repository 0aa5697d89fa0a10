"""Seeded randomized property sweep (shared helpers: property_cases.py).

(c) **Ledger honesty** — under RANDOM budgets and participant sets, the
declared byte ledger equals the actual array bytes, ``bytes_sent``
charges exactly the survivors, and the intra-pod columns are
internally consistent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from property_cases import D, QUANTIZERS
from repro.core import codec
from repro.dist import collectives
from repro.dist.sharding import chunk_ownership


# ------------------------------------------------------------ (c) ledger honesty


LEDGER_SPARSIFIERS = ["rand_k", "rand_k_spatial", "top_k", "wangni",
                      "induced", "identity"]


@pytest.mark.parametrize("ownership", [False, True],
                         ids=["monolithic", "ownership"])
@pytest.mark.parametrize("seed", range(60))
def test_ledger_honesty_random_budgets_participants(seed, ownership):
    """120 randomized cases: random sparsifier/quantizer/budget/participant
    draws; the declared schema must equal the actual payload bytes, the
    collectives ledger must charge exactly the survivors, and the intra-pod
    columns must be internally consistent."""
    rng = np.random.default_rng(seed)
    name = LEDGER_SPARSIFIERS[rng.integers(len(LEDGER_SPARSIFIERS))]
    d_block = int(rng.choice([32, 64, 128]))
    # wangni's fixed-capacity packing needs capacity_slots <= d_block
    k_hi = d_block // 2 if name == "wangni" else d_block
    k = int(rng.integers(1, k_hi + 1))
    q_name, q_ctor = QUANTIZERS[rng.integers(len(QUANTIZERS))]
    kw = {"transform": "avg"} if name == "rand_k_spatial" else {}
    if name == "identity":
        stages = [codec.Identity(d_block=d_block)]
    else:
        stages = [codec.SPARSIFIERS[name](k=k, d_block=d_block, **kw)]
    if q_ctor is not None:
        stages.append(q_ctor())
    pipe = codec.Pipeline(stages)

    n_total = int(rng.integers(2, 9))
    n_part = int(rng.integers(1, n_total + 1))
    if name == "rand_k_spatial" and n_part == 1:
        # the avg/opt interpolations are undefined at n=1 (rho = R/(n-1));
        # fl.server.resolve_pipeline rewrites to "one" — mirror it here
        stages[0] = stages[0].replace(transform="one")
        pipe = codec.Pipeline(stages)
    participants = np.sort(rng.choice(n_total, n_part, replace=False))
    d_flat = int(rng.integers(d_block, 4 * d_block + 1))
    tree = {"x": jnp.asarray(rng.standard_normal((n_total, d_flat)),
                             jnp.float32)}
    n_owners = int(rng.integers(2, 5)) if ownership else None

    key = jax.random.key(seed)
    _, info, _ = collectives.compressed_mean_tree(
        pipe, key, tree, participants=participants,
        ownership=n_owners,
    )

    # declared ledger == actual payload bytes for a real encode
    payload = pipe.encode_payload(key, 0, jnp.zeros((info["n_chunks"], d_block)))
    assert codec.check_against_schema(payload) == []
    assert payload.nbytes == pipe.payload_nbytes(info["n_chunks"])

    # the collectives ledger charges exactly the survivors
    assert info["n_clients"] == n_part
    assert info["n_total"] == n_total
    assert info["bytes_sent"] == n_part * pipe.payload_nbytes(info["n_chunks"])

    # intra-pod columns: the taken route's column is THE column, and the
    # standalone model reproduces the info dict exactly
    if ownership:
        assert info["n_shards"] == n_owners
        assert info["intra_pod_bytes"] == info["intra_pod_bytes_ownership"]
        model = collectives.intra_pod_traffic(
            pipe, n_part, info["n_chunks"], n_owners,
            plan=chunk_ownership(info["n_chunks"], n_owners))
        assert model == {k: info[k] for k in model}
    else:
        assert info["intra_pod_bytes"] == 0  # single logical shard


@pytest.mark.parametrize("seed", range(12))
def test_ledger_honesty_heterogeneous_budget_rounds(seed):
    """Randomized budget-group cohorts through fl.rounds: the per-round byte
    ledger equals the sum of each group's declared payload bytes, with and
    without ownership (24 cases)."""
    from repro.fl import Cohort, RoundConfig, get_task, run_rounds

    rng = np.random.default_rng(1000 + seed)
    n_clients = int(rng.integers(4, 9))
    budgets = tuple(int(rng.choice([4, 8, 16])) for _ in range(n_clients))
    task = get_task("dme", n_clients=n_clients, d=D, rho=0.9, seed=seed)
    pipe = codec.RandK(k=8, d_block=D)
    cohort = Cohort(n_clients=n_clients, dropout=float(rng.uniform(0, 0.4)),
                    budgets=budgets)
    cfgs = [RoundConfig(n_rounds=2, seed=seed),
            RoundConfig(n_rounds=2, seed=seed, ownership=True, n_owners=2)]
    hists = [run_rounds(task, pipe, cohort, cfg)[1] for cfg in cfgs]
    for hist in hists:
        for t in range(2):
            part = cohort.sample_round(seed, t)
            want = sum(
                codec.as_pipeline(pipe.replace(k=k_g)).payload_nbytes(1)
                * len(ids_g)
                for k_g, ids_g in cohort.budget_groups(part.survivors, pipe.k)
            )
            assert hist.bytes[t] == want
    # ownership changes the server's internal routing, never the wire ledger
    assert hists[0].bytes == hists[1].bytes
    assert hists[0].mse == hists[1].mse
