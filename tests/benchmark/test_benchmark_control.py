"""`correct` has to be able to fail. The control (the plain reference put
in the program's place, computed in fp8, the nearest precision below the
configuration's) and each fault a cell can have, at a size a test run
holds. The chip-size readings the real limits were set from are in
PERF.md; `benchmark/calibrate_train.py` and `calibrate_serve.py` make
them again."""
import numpy as np
import pytest

import bench_rehearse as br


def _train_pieces(seed=11):
    from benchmark import harness, traffic_gen, weights

    cell = harness.Cell.find("tiny-train", root=br.DATA, bench_dir=br.DATA)
    batches = traffic_gen.train_batches(cell.traffic, seed,
                                        cell.config["vocab_size"])
    ref = harness.load_module("reference", cell.config["reference"])
    make = lambda: weights.make_train_params(cell.config, seed)  # noqa: E731
    return cell, batches, ref, make


@pytest.mark.parametrize("kw,failing", [
    ({"precision": "fp8"}, "grad_norm_gap"),     # the control
    ({"rows": 1}, "loss2_rel"),                  # half of the batch left out
])
def test_train_control_and_half_batch_fail(kw, failing):
    from benchmark import compare

    cell, batches, ref, make = _train_pieces()
    opt = cell.config["optimizer"]
    reference = ref.first_steps(make(), batches[:3], cell.config, opt)
    other = ref.first_steps(make(), batches[:3], cell.config, opt, **kw)
    got, _ = compare.train_readings(other, reference, cell.limits)
    assert not got[failing].ok, {k: (c.value, c.limit)
                                 for k, c in got.items()}
    same, loose = compare.train_readings(reference, reference, cell.limits)
    assert all(c.ok for c in same.values()) and not loose
    held, loose = compare.train_readings(other, reference,
                                         {"grad_norm_gap": 1.0})
    assert set(held) == {"grad_norm_gap"} and len(loose) == 4


def test_train_state_left_unchanged_is_not_correct(monkeypatch):
    """A step that returns its state unchanged: the loss is computed, the
    update dropped. The parameters' change then reads 1."""
    from paddle_tpu.distributed.fleet.trainer import HybridTrainer

    real = HybridTrainer.step

    def frozen(self, ids, labels):
        import jax
        import jax.numpy as jnp

        keep = jax.tree.map(jnp.copy, (self.params, self.opt_state))
        loss = real(self, ids, labels)
        self.params, self.opt_state = keep
        return loss

    monkeypatch.setattr(HybridTrainer, "step", frozen)
    _, result, line = br.rehearse("tiny-train", seconds=0.3)
    assert line["correct"] is False
    assert line["compared"]["change_norm_gap"]["value"] == pytest.approx(
        1.0, abs=1e-3)


def test_train_half_batch_in_the_program_is_not_correct(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from paddle_tpu.distributed.fleet.trainer import HybridTrainer

    real = HybridTrainer.step
    monkeypatch.setattr(
        HybridTrainer, "step", lambda self, ids, labels: real(
            self, np.concatenate([ids[:1], ids[:1]]),
            np.concatenate([labels[:1], labels[:1]])))
    _, result, line = br.rehearse("tiny-train", seconds=0.3)
    assert line["correct"] is False


def test_serve_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced (the greedy sampler): what is
    served is no longer the reference's best."""
    from paddle_tpu.inference import serving

    real = serving._greedy_tokens_dev
    monkeypatch.setattr(serving, "_greedy_tokens_dev",
                        lambda logits: (real(logits) + 1) % 512)
    _, result, line = br.rehearse("tiny-serve", seconds=1.0)
    assert line["correct"] is False
    assert line["compared"]["served_logit_gap"]["value"] > \
        line["compared"]["served_logit_gap"]["limit"]


def test_serve_control_fp8_fails():
    """The control of a served model: at each position of served prompts
    and tokens, the gap of the token that fp8 puts first."""
    from benchmark import compare, harness, weights

    cell = harness.Cell.find("tiny-serve", root=br.DATA, bench_dir=br.DATA)
    _, result, line = br.rehearse("tiny-serve", seconds=1.0, seed=31)
    assert line["correct"] is True
    ref = harness.load_module("reference", cell.config["reference"])
    from benchmark.runners import serve

    model, engine, shapes = serve.build_engine(cell.config, 31)
    w = weights.make_like(shapes, cell.config, 31, donate=False)
    rng = np.random.default_rng(0)
    worst = 0.0
    for n in (60, 90, 40, 75):
        tokens = rng.integers(1, 512, n).tolist()
        full = np.asarray(ref.logits_at(w, tokens, 0, cell.config))
        low = np.asarray(ref.logits_at(w, tokens, 0, cell.config,
                                       precision="fp8"))
        worst = max(worst, compare.served_gap(full, low.argmax(-1)))
    assert worst > cell.limits["served_logit_gap"]
