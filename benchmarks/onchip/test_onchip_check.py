"""The comparison that decides `correct`.

* Each plain reference against what `Engine` serves (prefill, then
  decode through the arena) at a reduced size.
* The controls: the reference computed in int8 or fp8, precisions below
  the configurations' bfloat16, fails the limit that the served tokens
  pass.
* Faults planted under the timed path (a token altered where it is
  produced; a decode step that leaves the cache as it was) turn a whole
  run's `correct` false.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tinykit import TINY_CONFIGS, run_cell
from onchip_bench import check, serve, spec
from onchip_bench.traffic import Item


def _serve_tiny(conf_name: str, dtype: str, policy: str = "auto",
                n: int = 6, new: int = 12, **extra):
    from repro.configs import get_config
    from repro.serving import Engine
    from repro.serving.types import Request, SamplingParams
    conf = TINY_CONFIGS[conf_name]
    sizes = dict(conf["config"], dtype=dtype, kernel_policy=policy, **extra)
    ref_mod = spec.reference(conf)
    params = ref_mod.make_weights(sizes, 11)
    cfg = get_config(conf["model"], **sizes)
    rec = serve.Recorder(sizes)
    eng = Engine(cfg, params=params, capacity=4, max_len=48,
                 prefill_buckets=(16, 32), seed=3, on_token=rec.on_token)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(n):
        item = Item(i, 0.0, rng.integers(0, sizes["vocab"], 5 + 2 * i
                                         ).astype(np.int32), new, 0.0, 0, i)
        r = serve.Req(item)
        rec.reqs[f"r{i}"] = r
        reqs.append(r)
        eng.submit(Request(f"r{i}", item.prompt.tolist(),
                           SamplingParams(max_new_tokens=new)))
    while eng.n_queued or eng.n_active:
        eng.step()
    assert all(r.done for r in reqs)
    return ref_mod.Reference(sizes, 48), params, reqs


@pytest.mark.parametrize("conf_name,policy,extra", [
    ("tiny-lm", "auto", {}),
    ("tiny-lm", "pallas", {"attn_impl": "flash"}),
    ("tiny-ssm", "auto", {}),
])
def test_reference_matches_the_engine_in_float32(conf_name, policy, extra):
    ref, params, reqs = _serve_tiny(conf_name, "float32", policy, **extra)
    with jax.default_matmul_precision("highest"):
        gaps = check.served_gaps(ref, params, reqs, getattr(ref, "batch", 1))
    assert max(gaps) < 1e-4


#: a limit between the tiny models' readings on these requests: served
#: bfloat16 at most 0.004, int8 at least 0.0165, fp8 at least 0.032
TINY_CONTROL_LIMIT = 0.008


@pytest.mark.parametrize("quant", ["int8", "fp8"])
@pytest.mark.parametrize("conf_name", ["tiny-lm", "tiny-ssm"])
def test_control_is_not_correct(conf_name, quant):
    """The served bfloat16 tokens pass the limit; the reference computed
    in int8 or fp8, put in their place, fails it."""
    ref, params, reqs = _serve_tiny(conf_name, "bfloat16", n=8, new=30)
    limit = TINY_CONTROL_LIMIT
    served, ok = check.judge(ref, params, reqs, limit)
    control, ctl_ok = check.judge(ref, params, reqs, limit, quant)
    assert ok and not ctl_ok
    assert control > 3 * served


def test_sample_keeps_the_longest():
    def req(i, p, t, temp=0.0):
        r = serve.Req(Item(i, 0.0, np.zeros(p, np.int32), t, temp, 0, i))
        r.tokens = [0] * t
        return r
    pool = [req(i, 10 + i, 5) for i in range(20)] + [req(99, 5, 400),
                                                     req(98, 500, 500, 0.7)]
    got = check.sample(pool, 123, 4)
    assert got[0].item.index == 99          # the sampled one is skipped
    assert len(got) == 4 and len({r.item.index for r in got}) == 4
    assert [r.item.index for r in check.sample(pool, 123, 4)] == \
        [r.item.index for r in got]


def _alter_tokens(monkeypatch):
    from repro.serving.engine import Engine
    orig = Engine._emit

    def emit(self, slot_id, token):
        orig(self, slot_id, (token + 1) % self.cfg.vocab)
    monkeypatch.setattr(Engine, "_emit", emit)


def _freeze_cache(monkeypatch):
    from repro.serving.engine import Engine
    orig = Engine._make_decode

    def make(self, spec_):
        step = orig(self, spec_)

        def frozen(params, state):
            old = jax.tree_util.tree_map(jnp.copy, state["cache"])
            new, tok = step(params, state)
            return dict(new, cache=old), tok
        return frozen
    monkeypatch.setattr(Engine, "_make_decode", make)


@pytest.mark.parametrize("fault", [_alter_tokens, _freeze_cache])
@pytest.mark.parametrize("workload", ["tiny-lm.tiny_mixed",
                                      "tiny-ssm.tiny_offline"])
def test_a_broken_timed_path_is_not_correct(tiny_root, capsys, monkeypatch,
                                            fault, workload):
    root, here = tiny_root
    fault(monkeypatch)
    res = run_cell(root, here, workload, seed=2**33 + 1, capsys=capsys)
    assert res["correct"] is False
    gap = res["check"]["logit_gap"]
    assert gap["value"] > gap["limit"]
