"""The Nemotron-H client model (``repro.models.nemotron_h``) against the
plain float32 reference of the benchmark (``bench/fl_lm_reference.py``), at
a small Nemotron-shaped size on the CPU: d_model 64, Mamba2 4 heads x 16 in
2 groups with state 16 and chunk 8, GQA 4 query / 2 KV heads of 16, 16
routed experts of width 32 (top 2), a shared expert of width 64, a
vocabulary of 64, sequences of 32.

Tolerances: both sides compute in float32 with ``highest`` matmuls, so
what differs is the order of operations (the chunked SSD scan against the
step-by-step recurrence, grouped expert matmuls against dense ones, blocked
attention): logits agree to a few float32 ulps of their scale, 1e-4
relative; gradients, a longer chain of such sums, to 1e-3 of each leaf's
norm."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import fl_lm_inputs  # noqa: E402
from bench import fl_lm_reference as ref  # noqa: E402
from bench.drivers.fl_lm import model_config  # noqa: E402
from repro.configs.nemotron3_nano_30b_a3b import config as published  # noqa: E402
from repro.core.fl_round import _scans_clients  # noqa: E402
from repro.models import nemotron_h as nh  # noqa: E402

SMALL = {"hidden_size": 64, "mamba_num_heads": 4, "mamba_head_dim": 16,
         "n_groups": 2, "ssm_state_size": 16, "chunk_size": 8,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "num_experts_per_tok": 2, "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 64, "vocab_size": 64,
         "compute_dtype": "float32", "conv_kernel": 4, "norm_eps": 1e-5,
         "routed_scaling_factor": 2.5, "norm_topk_prob": True,
         "time_step_min": 0.001, "time_step_max": 0.1, "name": "small"}


def mc(pattern="ME*", held=8, first=0):
    return dict(SMALL, hybrid_override_pattern=pattern,
                n_routed_experts=held,
                held={"router_experts": 16, "expert_first": first})


def program(c):
    return model_config(c)


def init(key, c):
    return jax.jit(lambda k: fl_lm_inputs.init_params(k, c))(key)


@pytest.fixture(scope="module")
def setup():
    c = mc()
    params = init(jax.random.PRNGKey(0), c)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64)
    return c, params, x, y


def test_published_config_has_every_layer_and_width():
    cfg = published()
    assert len(cfg.pattern) == 52 and cfg.pattern.count("*") == 6
    assert cfg.pattern.count("E") == 23 and cfg.pattern.count("M") == 23
    assert (cfg.d_model, cfg.d_inner, cfg.n_experts, cfg.held) == (
        2688, 4096, 128, 128)
    assert (cfg.expert_ff, cfg.shared_ff, cfg.top_k) == (1856, 3712, 6)
    assert (cfg.vocab_size, cfg.norm_eps, cfg.routed_scale) == (
        131072, 1e-5, 2.5)


def test_forward_loss_and_gradients_equal_the_reference(setup, monkeypatch):
    """Attention in blocks of 8 queries, so that the blocks' causal key
    prefixes are exercised at sequence 32."""
    monkeypatch.setattr(nh, "ATTN_BLOCK", 8)
    c, params, x, y = setup
    cfg, rc = program(c), ref._thaw(ref._freeze(c))
    w = jnp.ones((2,))

    @jax.jit
    def both(params):
        return (nh.logits(params, x, cfg), ref.logits(params, x, rc),
                jax.value_and_grad(nh.loss, has_aux=True)(params, x, y, w,
                                                          cfg),
                jax.value_and_grad(ref.loss, has_aux=True)(params, x, y, w,
                                                           rc))
    with jax.default_matmul_precision("highest"):
        z, z_ref, ((l, aux), g), ((l_ref, counts), g_ref) = both(params)
    scale = float(jnp.max(jnp.abs(z_ref)))
    assert float(jnp.max(jnp.abs(z - z_ref))) <= 1e-4 * scale
    assert abs(float(l) - float(l_ref)) <= 1e-5 * abs(float(l_ref))
    np.testing.assert_array_equal(aux["expert_tokens"], counts)
    assert int(aux["tokens_dropped"]) == 0
    assert aux["expert_tiles"].shape == (1,)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * max(
            float(jnp.linalg.norm(b)), 1e-12)


def _moe_params(key):
    """One MoE layer's weights for all 16 experts."""
    return init(key, mc("E", held=16))["layers"][0]


def test_expert_shares_add_up_to_the_uncut_layer():
    """Experts 0-7 and 8-15, each share routing over all 16 and computing
    its own experts' terms, with the shared expert counted once, give the
    uncut layer of the reference."""
    full = _moe_params(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    cfg = program(mc("E"))

    @jax.jit
    def parts(full, x):
        halves = [nh.moe_mixer(dict(full, up=full["up"][lo:lo + 8],
                                    down=full["down"][lo:lo + 8]), x,
                               program(mc("E", held=8, first=lo)))
                  for lo in (0, 8)]
        shared = nh._mm(nh._relu2(nh._mm(x.reshape(-1, 64),
                                         full["shared_up"], cfg)),
                        full["shared_down"], cfg).reshape(x.shape)
        return halves, shared, ref._moe(full, x, ref._thaw(ref._freeze(
            mc("E", held=16))))
    with jax.default_matmul_precision("highest"):
        halves, shared, (whole, counts) = parts(full, x)
    assert all(int(h[1]["tokens_dropped"]) == 0 for h in halves)
    total = halves[0][0] + halves[1][0] - shared
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-4 * float(
        jnp.max(jnp.abs(whole)))
    np.testing.assert_array_equal(
        jnp.concatenate([halves[0][1]["expert_tokens"],
                         halves[1][1]["expert_tokens"]]), counts)


def _routed_to(p, *experts):
    """``p`` with a router that gives every token the same scores, and a
    correction bias that makes ``experts`` its top choices."""
    bias = jnp.zeros((16,)).at[jnp.asarray(experts)].set(1.0)
    return dict(p, router=jnp.zeros_like(p["router"]), bias=bias)


def _held_params(key, **changes):
    p = dict(_moe_params(key))
    return dict(p, up=p["up"][:8], down=p["down"][:8], **changes)


@pytest.mark.parametrize("seq", [32, 512])  # within one m-tile, across
def test_every_token_to_one_held_expert_is_computed(seq):
    """A router that sends every token to held expert 3 (and, the top 2
    being taken, to expert 0 with it): no assignment is dropped, whether
    expert 3's tokens lie within one m-tile of the grouped matmul (64
    tokens) or span several (1024), and the output is the reference's."""
    c = mc("E", held=8)
    p = _routed_to(_held_params(jax.random.PRNGKey(5)), 3)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, seq, 64))
    t, tm = 2 * seq, nh.GMM_TILING[0]
    spans = -(-2 * t // tm) - t // tm        # m-tiles of expert 3's rows
    assert (spans == 1) if seq == 32 else (spans >= 2)
    both = jax.jit(lambda p, x: (
        nh.moe_mixer(p, x, program(c)),
        ref._moe(p, x, ref._thaw(ref._freeze(c)))))
    with jax.default_matmul_precision("highest"):
        (y, aux), (y_ref, counts_ref) = both(p, x)
    counts = aux["expert_tokens"]
    assert int(aux["tokens_dropped"]) == 0
    assert int(counts[3]) == t and int(jnp.sum(counts)) == 2 * t
    np.testing.assert_array_equal(counts, counts_ref)
    assert float(jnp.max(jnp.abs(y - y_ref))) <= 1e-4 * float(
        jnp.max(jnp.abs(y_ref)))


@pytest.mark.parametrize("case", ["mostly_elsewhere", "none_held"])
def test_few_or_no_held_assignments_give_the_reference_and_its_gradients(
        case):
    """Most assignments go to experts 8-15, which this layer does not
    hold, so the held ones end part-way into an m-tile (the kernel leaves
    the rows past them unwritten, in its output and in its input's
    cotangent); or every one does, and the kernel runs no tile.  The loss
    and its gradients with respect to x, ``up`` and ``down`` are finite
    and the reference's."""
    c = mc("E", held=8)
    p = _held_params(jax.random.PRNGKey(8))
    if case == "mostly_elsewhere":
        p["bias"] = jnp.zeros((16,)).at[8:].set(0.03)
    else:
        p = _routed_to(p, 8, 9)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, 64))
    r = jax.random.normal(jax.random.PRNGKey(10), (2, 32, 64))
    cfg, rc = program(c), ref._thaw(ref._freeze(c))

    def grads(mixer):
        def f(up, down, x):
            y, aux = mixer(dict(p, up=up, down=down), x)
            return jnp.vdot(y, r), aux
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)(
            p["up"], p["down"], x)
    both = jax.jit(lambda: (grads(lambda q, x: nh.moe_mixer(q, x, cfg)),
                            grads(lambda q, x: ref._moe(q, x, rc))))
    with jax.default_matmul_precision("highest"):
        ((l, aux), g), ((l_ref, counts), g_ref) = both()
    held, tm = int(jnp.sum(counts)), nh.GMM_TILING[0]
    if case == "mostly_elsewhere":        # of 2 x 32 tokens x top 2
        assert 0 < held < 64 and held % tm != 0
    else:
        assert held == 0 and int(aux["expert_tiles"]) == 0
    np.testing.assert_array_equal(aux["expert_tokens"], counts)
    assert int(aux["tokens_dropped"]) == 0
    assert abs(float(l) - float(l_ref)) <= 1e-5 * abs(float(l_ref))
    for a, b in zip(g, g_ref):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * max(
            float(jnp.linalg.norm(b)), 1e-12)


def test_expert_tiles_are_counted_by_hand(monkeypatch):
    """Every token to experts 0 and 3 (400 tokens each), in m-tiles of
    128: expert 0's rows 0-399 fill tiles 0-3, expert 3's rows 400-799
    tiles 3-6, which the kernel visits once for each expert: 8 tiles."""
    monkeypatch.setattr(nh, "GMM_TILING", (128, 128, 128))
    c = mc("E", held=8)
    p = _routed_to(_held_params(jax.random.PRNGKey(11)), 3)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 200, 64))
    _, aux = jax.jit(lambda p, x: nh.moe_mixer(p, x, program(c)))(p, x)
    assert [int(n) for n in aux["expert_tokens"]] == [400, 0, 0, 400, 0,
                                                       0, 0, 0]
    assert int(aux["expert_tiles"]) == 8


@pytest.mark.parametrize("counts", [[400, 0, 0, 400], [0, 5, 128, 0, 1],
                                    [0, 0, 0], [127, 1, 128, 300]])
def test_expert_tiles_are_the_kernels_own_count(counts):
    """``expert_tiles`` is the number of m-tiles megablox's own group
    metadata gives the grouped matmul, for groups that start and end
    inside tiles, on tile edges, or are empty."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    sizes = jnp.asarray(counts, jnp.int32)
    _, tiles = make_group_metadata(
        group_sizes=sizes, m=1024, tm=128, start_group=jnp.int32(0),
        num_nonzero_groups=len(counts), visit_empty_groups=False)
    assert int(nh._expert_tiles(sizes, 128)) == int(tiles)


def test_client_model_is_trained_one_client_at_a_time(setup):
    c, params, x, y = setup
    model = nh.LMClient(program(c))
    assert _scans_clients(model) and hash(model) == hash(
        nh.LMClient(program(c)))
    assert not _scans_clients(lambda p, v: v)
    acc = model.accuracy(params, jnp.concatenate([x, x]),
                         jnp.concatenate([y, y]))
    assert 0.0 <= float(acc) <= 1.0


def _lm_round_inputs(c):
    """A tiny federated token dataset and FL state (M = 6, N = 3) from the
    benchmark's generator, one seed."""
    import json
    from bench import fl_lm_inputs, inputs
    from repro.core.fl_round import FLState
    from repro.core.reputation import ReputationState
    from repro.data.federated import FedData
    cell = json.loads((ROOT / "bench/configs/nemotron3_nano_fl_ep16.json")
                      .read_text())
    config = dict(cell, **c, clients_total=6, clients_per_round=3,
                  data_samples=[8.0, 16.0])
    traffic = {"data": {"seq_len": 32, "val_seqs": 4, "successors": 4,
                        "poison_ratio": 0.34}}
    g = jax.tree_util.tree_map(lambda a: a[0], fl_lm_inputs.grid_inputs(
        inputs.prng_key(5), 1, config, traffic))
    params = jax.tree_util.tree_map(lambda a: a[0], fl_lm_inputs.initial_params(
        inputs.prng_key(6), 1, config))
    state = FLState(params=params,
                    rep=ReputationState(ms=g["ms"], pi_count=g["pi_count"],
                                        ni_count=g["ni_count"]),
                    v_max=g["v_max"], distances=g["distances"], key=g["key"])
    data = FedData(**{f: g[f] for f in ("x", "y", "y_train", "mask",
                                        "sizes", "poisoned", "x_val",
                                        "y_val")})
    return state, data


def test_lm_round_traces_once_and_keeps_its_scopes():
    """``sweep_training`` with the client model (a C = 2 grid of DT
    deviations): the round is traced once, and the compiled program keeps
    the mixers' and the round stages' scopes."""
    from repro.core import fl_round
    from repro.core.stackelberg import GameConfig, TRACE_COUNTS
    c = mc()
    state, data = _lm_round_inputs(c)
    fls = [fl_round.FLConfig(n_selected=3, local_steps=2, server_steps=2,
                             lr=0.5, epsilon=e) for e in (0.0, 0.3)]
    states = jax.tree_util.tree_map(lambda a: a[None],
                                    fl_round._canon_state(state))
    seen, real = [], fl_round._sweep_training_jit

    class Recorded(Exception):
        pass

    def record(*a, **k):
        seen.append((a, k))
        raise Recorded
    try:
        fl_round._sweep_training_jit = record
        with pytest.raises(Recorded):
            fl_round.sweep_training(states, data, fls, GameConfig(),
                                    nh.LMClient(program(c)), rounds=2,
                                    data_axis="seed")
    finally:
        fl_round._sweep_training_jit = real
    (args, kwargs), = seen
    before = TRACE_COUNTS["run_round"]
    lowered = real.lower(*args, **kwargs)
    text = lowered.compile().as_text()
    assert TRACE_COUNTS["run_round"] - before == 1
    for scope in ("lm.mamba", "lm.moe", "lm.attn", "lm.head",
                  "fl.local_sgd", "fl.twin_sgd", "fl.roni", "fl.aggregate",
                  "sic_power"):
        assert scope in text, scope
    # the MoE counters join the metrics history: [..., R, N + 1, steps, n_moe]
    _, history = lowered.out_info
    tiles, tokens = history["expert_tiles"], history["expert_tokens"]
    assert tiles.shape[-4:] == (2, 4, 2, 1)
    assert tokens.shape[-5:] == (2, 4, 2, 1, c["n_routed_experts"])


def test_gradients_stay_finite_where_a_chunk_decays_far(setup):
    """dt·|A| summed over a chunk far past float32's exp range (dt ~ 5, A =
    -8, chunk 8): the SSD scan's masked upper triangle must not turn the
    backward pass into NaN (at the published widths a 128-step chunk
    reaches such sums)."""
    c = mc("M")
    cfg = program(c)
    p = init(jax.random.PRNGKey(7), c)
    layer = dict(p["layers"][0], dt_bias=jnp.full((4,), 5.0),
                 A_log=jnp.log(jnp.full((4,), 8.0)))
    p = dict(p, layers=(layer,))
    x = setup[2]
    g = jax.jit(jax.grad(lambda q: nh.loss(q, x, x, jnp.ones(2), cfg)[0]))(p)
    assert all(bool(jnp.all(jnp.isfinite(a)))
               for a in jax.tree_util.tree_leaves(g))
