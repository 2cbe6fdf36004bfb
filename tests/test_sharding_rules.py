"""Properties of the logical-axis sharding resolver (hypothesis)."""
import jax
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (RuleSet, resolve_spec, serve_rules,
                                        train_rules)


def _mesh_1dev(shape, axes):
    # A mesh over a single device repeated is impossible; use abstract mesh
    # for resolution-only tests.
    return jax.sharding.AbstractMesh(shape, axes)


MESH = _mesh_1dev((2, 4, 8), ("pod", "data", "model"))


def test_divisibility_fallback():
    rules = RuleSet({"heads": ("model",)})
    # 10 % 8 != 0 -> replicated
    assert resolve_spec((10,), ("heads",), rules, MESH) == P()
    assert resolve_spec((16,), ("heads",), rules, MESH) == P("model")


def test_prefix_greedy_multi_axis():
    rules = RuleSet({"batch": ("pod", "data", "model")})
    # 8 = 2*4 -> uses (pod, data); model would exceed divisibility
    assert resolve_spec((8,), ("batch",), rules, MESH) == P(("pod", "data"))
    assert resolve_spec((64,), ("batch",), rules, MESH) == \
        P(("pod", "data", "model"))
    assert resolve_spec((2,), ("batch",), rules, MESH) == P("pod")


def test_no_double_use():
    rules = RuleSet({"a": ("model",), "b": ("model",)})
    spec = resolve_spec((8, 8), ("a", "b"), rules, MESH)
    assert spec == P("model")  # second dim can't reuse model


def test_unknown_logical_name_replicates():
    rules = RuleSet({})
    assert resolve_spec((128, 128), ("x", "y"), rules, MESH) == P()


@settings(max_examples=50, deadline=None)
@given(
    dims=st.lists(st.sampled_from([1, 2, 3, 4, 6, 8, 16, 30, 64]),
                  min_size=1, max_size=4),
    names=st.lists(st.sampled_from(["batch", "heads_act", "mlp_act",
                                    "p_embed", "kv_seq", None]),
                   min_size=1, max_size=4),
)
def test_resolver_properties(dims, names):
    n = min(len(dims), len(names))
    dims, names = tuple(dims[:n]), tuple(names[:n])
    rules = train_rules()
    spec = resolve_spec(dims, names, rules, MESH)
    sizes = dict(zip(MESH.axis_names, MESH.axis_sizes))
    used = []
    for i, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        prod = 1
        for a in axes:
            assert a not in used, "axis used twice"
            used.append(a)
            prod *= sizes[a]
        assert dims[i] % prod == 0, "non-dividing assignment"


def test_serve_rules_batch1_shards_kvseq_everywhere():
    rules = serve_rules(False, batch1=True)
    spec = resolve_spec((1, 8, 524288, 128),
                        ("batch", "kv_heads_act", "kv_seq", None),
                        rules, MESH)
    assert spec[2] == ("pod", "data", "model")
