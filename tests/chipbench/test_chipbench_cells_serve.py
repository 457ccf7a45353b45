"""Serve cells driven end to end on the CPU at a tiny size: the harness
without its look for a chip.  A sound run is correct, the fp8 control
is not, and neither is a run whose tokens are altered where produced."""
import json

import jax

from chipbench import cells, serve, traffic
from chipbench.bench import arch_module, check
from chipbench_tiny import tiny_cell

SEED = 2**31 + 4242


def _run(cell, seed=SEED):
    lines = []
    cells.run(cell, jax.devices()[:1], seed=seed, seconds=2.0,
              out=lines.append)
    return json.loads(lines[-1])


def test_batch_run_result_line():
    out = _run(tiny_cell("qwen3-32b-serve.batch"))
    assert out["correct"] is True
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks" and "logit_gap" in out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_chat_is_correct_and_its_control_is_not():
    # the same engine and check as a run, on a fixed set of requests run
    # to completion, so the tokens compared do not depend on the clock
    cell = tiny_cell("qwen3-32b-serve.chat")
    engine, params = serve.setup(cell, SEED)
    reqs = traffic.open_loop(cell.mix, SEED, 4.0, cell.config["vocab_size"])
    done = engine.generate([r.prompt for r in reqs],
                           serve._sampling(cell.mix["output"]["max"]))
    picks = [serve.Served(i, 0.0, r.prompt, len(d.tokens), tokens=d.tokens)
             for i, (r, d) in enumerate(zip(reqs, done))]
    serve.free(engine)
    prog, control = serve.logit_gaps(arch_module(cell.config), params,
                                     cell.config, picks, control=True)
    assert check(cell, "logit_gap", prog).ok
    assert not check(cell, "logit_gap", control).ok


def test_chat_run_result_line():
    out = _run(tiny_cell("qwen3-32b-serve.chat"))
    assert out["correct"] is True
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}


def test_an_altered_token_is_caught(monkeypatch):
    import repro.serving.engine as engine
    real = engine.sample_tokens

    def off_by_one(logits, *a):
        return (real(logits, *a) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "sample_tokens", off_by_one)
    out = _run(tiny_cell("qwen3-32b-serve.chat"))
    assert out["correct"] is False
    c = out["checks"]["logit_gap"]
    assert c["value"] > c["limit"]
