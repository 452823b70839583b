"""A model module's expert layout: which layers keep routed experts in
the host store and which of each layer's published experts this chip
holds. The default (no ``layout``) fills the store as every layer with
every expert; a declared layout with a dense first layer and a held
share fills only those, and routes and popularity take rows that list
fewer than ``k`` experts, by published id."""
import types

import numpy as np
import pytest

import driver
import spec as bspec
import tiny
from repro.core.expert_store import ExpertStore
from repro.core.trace import StepTrace

GQA = bspec.load_module(bspec.BENCH / "models" / "gqa_moe.py", "gqa_moe_l")


class StubWeights:
    """Three layers; ``experts(l)`` stacks four experts of d 2, F 3 whose
    values name the layer and the row of the stack."""

    def experts(self, layer):
        base = 100.0 * layer + np.arange(4, dtype=np.float32)[:, None, None]
        return {"w1": base + np.zeros((4, 2, 3), np.float32),
                "w3": base + np.ones((4, 2, 3), np.float32),
                "w2": base + np.zeros((4, 3, 2), np.float32)}


STUB = types.SimpleNamespace(layout=lambda c: ([1, 2], [4, 5, 6, 7], 8))


def _step(layer, rows):
    """A trace record of one step at ``layer``: rows of (rid, token,
    routed ids)."""
    acts = sorted({e for _, _, a in rows for e in a})
    return StepTrace(prompt_id=-1, token_idx=0, layer=layer,
                     activated=tuple(acts), gate_weights=(), cache_before=(),
                     cache_after=(), hits=(), misses=(), evicted=(),
                     request_ids=tuple(r for r, _, _ in rows),
                     request_token_idx=tuple(t for _, t, _ in rows),
                     request_activated=tuple(tuple(a) for _, _, a in rows))


def test_default_layout_is_every_layer_and_expert():
    d = GQA.dims(tiny.TINY_CONFIG)
    layers, held, experts = bspec.expert_layout(GQA, tiny.TINY_CONFIG)
    assert layers == list(range(d.L)) and held == list(range(d.E))
    assert experts == d.E == 8


def test_declared_layout_is_the_modules():
    assert bspec.expert_layout(STUB, {}) == ([1, 2], [4, 5, 6, 7], 8)


def test_default_store_is_the_full_store():
    """The store the default layout fills holds the keys, in order, and
    the bytes that every layer's every expert gave before layouts."""
    import jax
    c = tiny.TINY_CONFIG
    w = GQA.Weights(c, 2**31 + 17, 1.0)
    d = w.dims
    old = ExpertStore()
    for l in range(d.L):
        ex = jax.device_get(w.experts(l))
        for e in range(d.E):
            old.put((l, e), {k: v[e] for k, v in ex.items()})
    layers, held, _ = bspec.expert_layout(GQA, c)
    new = driver.fill_store(w, layers, held)
    assert new.keys() == old.keys() == [(l, e) for l in range(d.L)
                                        for e in range(d.E)]
    assert new.total_nbytes() == old.total_nbytes() > 0
    for key in old.keys():
        a, b = old.fetch(key), new.fetch(key)
        assert a.keys() == b.keys()
        for name in a:
            assert a[name].dtype == b[name].dtype
            np.testing.assert_array_equal(a[name], b[name])


def test_declared_store_holds_the_share():
    """Dense layer 0 and held ids 4..7 of 8: the store holds layers 1
    and 2 only, the i-th stacked expert under its published id."""
    layers, held, _ = bspec.expert_layout(STUB, {})
    store = driver.fill_store(StubWeights(), layers, held)
    assert set(store.keys()) == {(l, e) for l in (1, 2) for e in range(4, 8)}
    for l, e in store.keys():
        np.testing.assert_array_equal(
            store.fetch((l, e))["w1"], np.full((2, 3), 100.0 * l + e - 4))
    assert store.total_nbytes() == 8 * 3 * 2 * 3 * 4


def test_routes_pad_short_rows():
    dims = types.SimpleNamespace(L=3, k=3)
    trace = types.SimpleNamespace(steps=[
        _step(1, [(7, 0, (4, 5, 6)), (8, 0, (5,))]),
        _step(2, [(7, 0, (7,)), (8, 0, ())]),
        _step(1, [(7, 1, ())]),
        _step(2, [(7, 1, (4, 6))]),
    ])
    r = driver._routes(trace, [7, 8], dims)
    assert r[7].shape == (2, 3, 3) and r[8].shape == (1, 3, 3)
    assert (r[7][:, 0] == -1).all() and (r[8][:, 0] == -1).all()
    assert r[7][0, 1].tolist() == [4, 5, 6]
    assert r[7][0, 2].tolist() == [7, -1, -1]
    assert r[7][1, 1].tolist() == [-1, -1, -1]
    assert r[7][1, 2].tolist() == [4, 6, -1]
    assert r[8][0, 1].tolist() == [5, -1, -1]
    assert r[8][0, 2].tolist() == [-1, -1, -1]


def test_popularity_by_published_id():
    records = [_step(1, [(7, 0, (4, 7)), (8, 0, (7,))]),
               _step(2, [(7, 0, ()), (8, 0, (5, 6, 7))]),
               _step(2, [(7, 1, (-1, 6))])]
    pop = driver.popularity(records, 3, 8)
    assert pop.shape == (3, 8)
    assert pop[0].tolist() == [0] * 8
    assert pop[1].tolist() == [0, 0, 0, 0, 1, 0, 0, 2]
    assert pop[2].tolist() == [0, 0, 0, 0, 0, 1, 2, 1]


def test_reference_refuses_a_padded_route():
    """The gqa_moe reference follows routes of exactly k experts; a -1
    would mark expert E - 1, so it is refused."""
    c = tiny.TINY_CONFIG
    w = GQA.Weights(c, 2**31 + 19, 1.0)
    d = w.dims
    seq = [1, 2, 3]
    route = np.tile(np.arange(d.k), (len(seq), d.L, 1))
    route[1, 0, -1] = -1
    with pytest.raises(AssertionError, match="fewer than k"):
        GQA.reference_logits(w, [seq], [route], 0.1)
