"""Plugin registries (repro.run.registry): registration contract,
duplicate rejection, did-you-mean errors, discovery surface."""
import pytest

from repro.run.registry import DuplicateRegistrationError, Registry
from repro.run import available


def test_register_decorator_and_mapping_protocol():
    reg = Registry("widget")

    @reg.register("alpha")
    def alpha():
        return 1

    @reg.register()
    def beta():
        return 2

    assert reg["alpha"] is alpha and reg["beta"] is beta
    assert sorted(reg) == ["alpha", "beta"] == reg.names()
    assert len(reg) == 2 and "alpha" in reg and "gamma" not in reg
    assert dict(reg) == {"alpha": alpha, "beta": beta}


def test_duplicate_name_rejected():
    reg = Registry("widget")
    reg.add("a", 1)
    with pytest.raises(DuplicateRegistrationError, match="already"):
        reg.add("a", 2)
    assert reg["a"] == 1                     # original entry untouched
    with pytest.raises(ValueError):
        reg.add("", 3)
    with pytest.raises(ValueError):
        reg.add(None, 3)


def test_unknown_name_error_lists_alternatives():
    reg = Registry("widget")
    reg.add("alpha", 1)
    reg.add("beta", 2)
    with pytest.raises(KeyError) as e:
        reg["gamma"]
    msg = str(e.value)
    assert "widget" in msg and "gamma" in msg
    assert "alpha" in msg and "beta" in msg


def test_stack_registries_carry_the_zoos():
    """The legacy dict surfaces ARE the registries now — same names,
    same objects, plus the did-you-mean KeyError."""
    from repro.core.aggregators import AGGREGATORS, cgc_sum
    from repro.core.byzantine import ATTACKS, sign_flip
    from repro.dist import AGG_FNS
    from repro.launch.engine import STRATEGIES, EchoDpStrategy

    assert AGGREGATORS["cgc"] is cgc_sum
    assert ATTACKS["sign_flip"] is sign_flip
    assert STRATEGIES["echo_dp"] is EchoDpStrategy
    assert set(STRATEGIES) == {"replicated", "fsdp", "echo_dp"}
    assert {"mean", "cgc", "median", "trimmed_mean", "krum"} <= set(AGG_FNS)
    with pytest.raises(KeyError, match="sign_flip"):
        ATTACKS["sing_flip"]
    with pytest.raises(KeyError, match="replicated"):
        STRATEGIES["replicatd"]


def test_available_reports_every_kind():
    names = available()
    assert {"aggregators", "collective_aggregators", "attacks",
            "train_strategies", "norm_backends", "scale_backends",
            "paged_attn_backends"} <= set(names)
    assert "cgc" in names["aggregators"]
    assert "cgc" in names["collective_aggregators"]
    assert "sign_flip" in names["attacks"]
    assert names["train_strategies"] == ["echo_dp", "fsdp", "replicated"]
    for kind in ("norm_backends", "scale_backends", "paged_attn_backends"):
        assert names[kind] == ["jnp", "pallas"]


def test_backend_switch_validates_against_registry():
    from repro.kernels import ops

    with pytest.raises(ValueError) as e:
        ops.set_norm_backend("cuda")
    assert "jnp" in str(e.value) and "pallas" in str(e.value) \
        and "auto" in str(e.value)
    # a newly registered backend becomes selectable with no ops.py edit
    from repro.run.registry import NORM_BACKENDS
    NORM_BACKENDS.add("test_dummy", lambda leaves: 0.0)
    try:
        ops.set_norm_backend("test_dummy")
        assert ops.norm_backend() == "test_dummy"
    finally:
        ops.set_norm_backend("auto")
        NORM_BACKENDS._entries.pop("test_dummy")
