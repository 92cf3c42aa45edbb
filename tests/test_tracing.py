"""The program names its own work for a profiler (docs/serving.md,
"Tracing a server"): the compiled decode step's HLO ops carry the
``bind`` / ``restack`` / ``<kind>/<node>`` scopes in their ``op_name``,
the scopes change nothing but that metadata, and ``ContinuousBatcher``
writes its host spans, with their arguments, into a profiler trace."""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.models.model_zoo import build_model
from repro.serve import ContinuousBatcher, Request, ServeEngine

B, MAX_SEQ = 2, 32


def _engine(arch):
    cfg = dataclasses.replace(smoke_variant(get_config(arch)), dtype="float32")
    api = build_model(cfg)
    eng = ServeEngine(api=api, batch_size=B, max_seq=MAX_SEQ)
    eng.load(api.init(jax.random.PRNGKey(0)))
    return eng


def _step_text(eng):
    tok = jnp.zeros((B,), jnp.int32)
    cache = eng.api.cache_init(B, MAX_SEQ)
    return eng.decode_fn().lower(eng.params, cache, tok, tok).compile().as_text()


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("arch,mixer", [("qwen3-4b", "cache_update/L0."),
                                        ("mamba2-2.7b", "ssm_decode/L0.")])
def test_compiled_decode_step_names_its_scopes(arch, mixer):
    names = _op_names(_step_text(_engine(arch)))
    for scope in ("jit(step)/bind/", "jit(step)/restack/", "jit(step)/matmul/L0.",
                  "jit(step)/matmul/lm_head_proj/", "jit(step)/" + mixer):
        assert any(n.startswith(scope) for n in names), (scope, sorted(set(names))[:20])


def _strip_metadata(text):
    """The computations alone (no module header, no stack-frame tables),
    without metadata."""
    body = [ln for ln in text.splitlines() if ln.startswith(("%", "ENTRY", " ", "}"))]
    return re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(body))


def test_scopes_change_only_metadata(monkeypatch):
    """The compiled step with every scope turned into a no-op is the same
    program, instruction for instruction, once metadata is stripped."""
    import contextlib

    eng = _engine("qwen3-4b")
    scoped = _step_text(eng)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _step_text(_engine("qwen3-4b"))
    assert "jit(step)/bind/" in scoped and "jit(step)/bind/" not in plain
    assert _strip_metadata(scoped) == _strip_metadata(plain)


SPANS = ("step", "admit", "prefill", "first_token", "slot_write", "inputs",
         "decode", "sample")


def test_batcher_writes_its_spans_with_their_arguments(tmp_path):
    from jax.profiler import ProfileData

    eng = _engine("qwen3-4b")
    rng = np.random.RandomState(0)
    reqs = [Request(uid=uid, prompt=rng.randint(0, 64, size=4).astype(np.int32),
                    max_new_tokens=3, arrival=a)
            for uid, a in ((11, 0), (12, 0), (13, 1))]
    ContinuousBatcher(eng).run(reqs)            # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        ContinuousBatcher(eng).run(reqs)
    path = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))[0]
    spans = [(e.name, dict(e.stats)) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events if e.name in SPANS]
    seen = {name for name, _ in spans}
    assert seen == set(SPANS)
    for name in ("admit", "prefill", "first_token", "slot_write"):
        assert sorted(a["uid"] for n, a in spans if n == name) == [11, 12, 13], name
    assert all(a["prompt_len"] == 4 for n, a in spans if n == "prefill")
    steps = [a for n, a in spans if n == "step"]
    assert [a["step"] for a in steps] == list(range(len(steps)))
    # two slots, three requests: the third waits a step for a free slot
    assert steps[0]["live"] == 0 and steps[0]["queued"] == 3
    assert max(a["live"] for a in steps) == B
