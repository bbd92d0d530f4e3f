"""The state-space decode step's kernel (ops/state_step.py) in interpret mode
against ``state_space.scan_step``, the rule that picks it
(``state_space.step_path``), and the engine's decode program with it as the
mixer's step against the same program with ``scan_step``.

(a) the kernel at the published widths of a head (128 x 256), 2 groups of
    B and C, several heads a group: ``y`` and the layer it writes to float32
    rounding, every other layer and every slot that is not decoding bit for
    bit, over blocks within a group and over whole groups;
(b) the path rule: XLA on the CPU, the kernel on a TPU for one float32 row a
    slot in whole lane tiles, XLA for a chunk, a bf16 state or narrow heads;
(c) a tiny Falcon-H1 served with the kernel as the decode step's recurrence
    emits the tokens it emits with ``scan_step``, and ``state_kernel_layers``
    says which.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.config import StateSpaceMixer
from ddl25spring_tpu.models import state_space
from ddl25spring_tpu.ops import state_step as ss
from ddl25spring_tpu.serving import engine as eng

from test_state_space import (CHUNK, LENGTHS, PAGED, description,  # noqa: F401
                              sequences, served, weights)

LAYERS, SLOTS, HEADS, P, N, GROUPS = 3, 5, 8, 128, 256, 2
MIXER = StateSpaceMixer(d_inner=HEADS * P, heads=HEADS, head_dim=P,
                        groups=GROUPS, state=N, conv=4, chunk=256)
DECODING = np.array([True, False, True, True, False])


def step_inputs(seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    store = f(LAYERS, SLOTS, HEADS, P, N)
    # a state that holds -0.0 keeps it where the slot is not decoding
    store = store.at[:, 1, 0, 0, :4].set(-0.0)
    dt = jnp.where(DECODING[:, None], jax.nn.softplus(f(SLOTS, HEADS)), 0.0)
    return (store, f(SLOTS, HEADS, P), dt, -jnp.exp(f(HEADS) * 0.5),
            f(SLOTS, GROUPS, N), f(SLOTS, GROUPS, N))


def same_bits(a, b) -> bool:
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ------------------------------------------------------------- (a) kernel

@pytest.mark.parametrize("heads_per_block", [2, 4, 8])
def test_the_kernel_is_the_step_and_leaves_every_other_row(heads_per_block):
    """Blocks of 2 and 4 heads lie in one group of 4, a block of 8 over both
    groups. Float32 sums over the state in another order: 1e-5 of values
    of order 10."""
    store, x, dt, a, b, c = step_inputs()
    layer = 1
    y, new = ss.state_step(store, jnp.int32(layer), x, dt, a, b, c,
                           ss.plan(jnp.asarray(DECODING)),
                           heads_per_block=heads_per_block, interpret=True)
    want_y, want_s = state_space.scan_step(x, dt, a, b, c, store[layer],
                                           MIXER)
    d = DECODING
    scale = float(jnp.abs(want_y[d]).max())
    np.testing.assert_allclose(y[d], want_y[d], rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(new[layer][d], want_s[d], rtol=1e-6,
                               atol=1e-6)
    assert float(jnp.abs(y[~d]).max()) == 0.0
    assert same_bits(new[layer][~d], store[layer][~d])
    for other in set(range(LAYERS)) - {layer}:
        assert same_bits(new[other], store[other])


def test_with_no_slot_decoding_the_store_keeps_its_bits():
    store, x, dt, a, b, c = step_inputs(1)
    none = jnp.zeros(SLOTS, bool)
    y, new = ss.state_step(store, jnp.int32(2), x, dt * 0, a, b, c,
                           ss.plan(none), interpret=True)
    assert same_bits(new, store)
    assert float(jnp.abs(y).max()) == 0.0


def test_the_plan_lists_the_decoding_slots_first():
    order, count, decoding = ss.plan(jnp.asarray(DECODING))
    assert list(np.asarray(order)) == [0, 2, 3, 1, 4]
    assert int(count[0]) == 3
    assert list(np.asarray(decoding)) == list(DECODING)


@pytest.mark.parametrize("heads, groups, p, n, want", [
    (32, 2, 128, 256, 16),      # Falcon-H1: 2 MiB a block, one group
    (8, 2, 128, 256, 8),        # two whole groups of 4
    (6, 2, 128, 1024, 3),       # a group of 3, and 4 heads are over 2 MiB
    (32, 2, 1024, 1024, 1),     # a head alone is over 2 MiB
])
def test_the_block_rule(heads, groups, p, n, want):
    assert ss.tiling(heads, groups, p, n) == want


# --------------------------------------------------------- (b) path rule

def test_the_path_rule(monkeypatch):
    f32 = dict(d_inner=32 * 128, heads=32, head_dim=128, groups=2, state=256,
               conv=4, chunk=256)
    xla = {"impl": "xla", "interpret": None}
    assert state_space.step_path(StateSpaceMixer(**f32), 1) == xla
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert state_space.step_path(StateSpaceMixer(**f32), 1) == {
        "impl": "pallas", "interpret": False}
    assert state_space.step_path(StateSpaceMixer(**f32), 256) == xla
    assert state_space.step_path(
        StateSpaceMixer(**f32, state_dtype="bfloat16"), 1) == xla
    assert state_space.step_path(description().mixer, 1) == xla  # 8 x 16


# ------------------------------------------------------------- (c) engine

def test_engine_with_the_kernel_serves_what_scan_step_serves(
        weights, sequences, monkeypatch):
    """The decode program with the kernel as the mixer's step (interpret
    mode; ``step_path`` replaced, as the grouped attention's test replaces
    its path function): the same tokens over several decode steps, slots
    joining and leaving; the dispatch spans count the kernel's layers."""
    desc = description()
    requests = [(seq[:p], 6) for seq, (p, _) in zip(sequences, LENGTHS)]
    plain = eng.Engine(weights, desc, PAGED, 2, prefill_chunk=CHUNK)
    want = served(plain, requests)

    def kernel_path(mx, t):
        assert (mx.head_dim, mx.state) == (8, 16)
        return {"impl": "pallas" if t == 1 else "xla", "interpret": True}

    monkeypatch.setattr(state_space, "step_path", kernel_path)
    engine = eng.Engine(weights, desc, PAGED, 2, prefill_chunk=CHUNK)
    seen = []
    record = engine.spans

    def spans(name, **counters):
        if name == "engine.decode.dispatch":
            seen.append(counters["state_kernel_layers"])
        return record(name, **counters)

    engine.spans = spans
    assert served(engine, requests) == want
    assert seen and set(seen) == {desc.n_layers}
    assert plain._state_kernel_layers == 0
