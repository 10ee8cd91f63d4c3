"""The serving cell's correctness check at a size a test run can hold:
the run's own path on the CPU (the look for a chip skipped), once sound,
once with a token altered where the engine produces it, and the control
(the reference at float8 in the program's place) against the cell's
limit."""

import json

import pytest

import run
from conftest import HERE

CELL = "qwen2-0.5b.reasoning-steady"
TRAFFIC = json.loads((HERE / "traffic" / f"{CELL}.json").read_text())
LIMIT = TRAFFIC["check"]["limits"]["logit_gap_max"]
# the cell's architecture at its published widths and vocabulary but two
# layers, a slot pool and prompts sized for the CPU; the check and its
# limit are the cell's.  Narrower widths shrink the logits' spread and
# with it every gap, so the control would no longer cross the limit.
SMALL = {"config": {"num_hidden_layers": 2},
         "traffic": {"deployment": {"n_slots": 4, "max_len": 256},
                     "arrivals": {"kind": "poisson", "rate_per_s": 4.0},
                     "preroll_s": 1.0,
                     "prompt_len": {"dist": "lognormal", "median": 24,
                                    "sigma": 0.5, "min": 8, "max": 48},
                     "output_len": {"dist": "lognormal", "median": 12,
                                    "sigma": 0.5, "min": 4, "max": 32}}}


def _run(seed):
    """One run of the cell's driver as ``run.py`` makes it, at ``SMALL``
    and on the CPU's device."""
    import time

    import jax

    spec = run.resolve(CELL)
    for part, changes in SMALL.items():
        spec[part] = {**spec[part], **changes}
    driver = run.load_module(spec["driver"])
    return driver.run(run.Run(
        cell=spec["cell"], config=spec["config"], traffic=spec["traffic"],
        seed=seed, seconds=3.0, trace=False, t_start=time.perf_counter(),
        devices=jax.devices()[:1], peaks={}))


def test_sound_run_is_correct():
    out = _run(2**33 + 5)
    assert out.correct, out
    assert out.compared["logit_gap_max"][0] <= LIMIT
    assert out.attempted > 0 and out.failed == 0


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve.engine import ContinuousEngine

    step = ContinuousEngine.step

    def altered(self):
        retired = step(self)
        for req in self._slot_req + retired:
            if req is not None and len(req.output) == 3:
                req.output[-1] = (req.output[-1] + 1) % 151936
        return retired

    monkeypatch.setattr(ContinuousEngine, "step", altered)
    out = _run(2**33 + 5)
    assert not out.correct, out
    assert out.compared["logit_gap_max"][0] > LIMIT
    assert out.failed > 0


@pytest.mark.parametrize("seed", [11, 2**31 + 7, 2**33 + 9])
def test_control_fails_the_limit(seed):
    """The reference at float8 in the program's place, read at every
    position of 12 sequences of 160 tokens (a run checks 12 requests), at
    the published widths with two layers: its widest gap crosses the
    cell's limit, as it does on the chip at full depth."""
    import numpy as np

    from reference import decoder

    spec = run.resolve(CELL)
    driver = run.load_module(spec["driver"])
    cfg = {**spec["config"], **SMALL["config"]}
    rng = np.random.default_rng(seed)
    records = [(rng.integers(0, cfg["vocab_size"], 32, dtype=np.int32),
                list(rng.integers(0, cfg["vocab_size"], 128)))
               for _ in range(12)]
    served, control = driver.check_served(cfg, decoder.seed_key(seed),
                                          records, 256, control=True)
    assert max(control) > LIMIT, control
