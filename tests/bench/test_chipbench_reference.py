"""The plain float32 reference agrees with the program's model at a tiny
size (both in float32, on the same benchmark-made weights)."""
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import run, weights  # noqa: E402
from chipbench.reference import dense  # noqa: E402

TINY = json.loads((Path(__file__).parent / "data" / "tiny.json").read_text())


def test_reference_matches_the_model_in_float32():
    from repro.models import model
    conf = dict(TINY, torch_dtype="float32")
    _, cfg = run.program_config(conf)
    w = weights.make(conf, 123)
    toks = np.random.default_rng(0).integers(0, conf["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.prefill(cfg, w, jnp.asarray(toks)[None])
    got = np.asarray(logits[0], np.float32)
    h = dense.hidden(w, conf, toks.tolist())
    ref = np.asarray(jnp.matmul(h, w["head"], precision=dense.HI))
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)
    mx, am = dense.best(w, h)
    np.testing.assert_allclose(np.asarray(mx), ref.max(-1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(am), ref.argmax(-1))


def test_served_gaps_read_zero_for_the_reference_argmax():
    conf = dict(TINY, torch_dtype="float32")
    w = weights.make(conf, 7)
    prompt = list(range(3, 20))
    seq, served = list(prompt), []
    for _ in range(6):             # greedy decode with the reference itself
        h = dense.hidden(w, conf, seq)
        _, am = dense.best(w, h[-1:])
        served.append(int(am[0]))
        seq.append(served[-1])
    gaps = dense.served_gaps(w, conf, prompt, served)
    assert gaps.shape == (6,) and float(gaps.max()) < 1e-5
    wrong = [(t + 1) % conf["vocab_size"] for t in served]
    assert float(dense.served_gaps(w, conf, prompt, wrong).min()) > 0
