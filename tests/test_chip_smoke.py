"""``chip_smoke.py`` on the CPU: the same phase functions the chip run
calls, at tiny sizes with Pallas interpreted — the dry run the on-chip
measurement guide asks for before a command is sent to the chip — plus
the three ways a run without a chip must fail."""

import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from znicz_tpu.core.backends import TPUDevice, XLADevice  # noqa: E402
from znicz_tpu.core.config import root  # noqa: E402

TRAINER = dict(minibatch_size=4, n_classes=8, input_size=67, n_train=8)
LM = dict(n_layers=1, d=128, heads=2, seq_len=128, minibatch_size=2,
          loss_chunks=2, lr=0.05)


@pytest.fixture(scope="module", autouse=True)
def interpreted_pallas():
    prev = root.common.engine.get("pallas_interpret", False)
    root.common.engine.pallas_interpret = True
    yield
    root.common.engine.pallas_interpret = prev


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """A seeded character corpus small enough for a handful of
    128-token windows (the shipped synthetic one is ~1,000)."""
    d = tmp_path_factory.mktemp("corpus")
    rng = np.random.default_rng(0)
    alphabet = "abcdefgh \n"
    for name, n in (("train.txt", 1500), ("test.txt", 300)):
        (d / name).write_text("".join(
            alphabet[i] for i in rng.integers(0, len(alphabet), n)))
    (d / ".synth_version").write_text("1")
    return str(d)


@pytest.fixture(scope="module")
def trainer():
    return chip_smoke.run_trainer(XLADevice(), **TRAINER)


@pytest.fixture(scope="module")
def lm(corpus, tmp_path_factory):
    w, losses, facts = chip_smoke.run_lm_trainer(
        XLADevice(), data_dir=corpus, interpret=True, **LM)
    pkg = str(tmp_path_factory.mktemp("pkg") / "lm.npz")
    w.step.export_lm(pkg)
    return w, losses, facts, pkg


# -- no chip, no pass ---------------------------------------------------------

def test_main_without_a_chip_exits_nonzero_before_any_phase():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    assert "==" not in proc.stdout            # no phase banner
    assert '"ok"' not in proc.stdout          # no result line
    assert "no TPU" in proc.stderr


def test_tpu_device_without_a_chip_raises():
    with pytest.raises(RuntimeError, match="needs a TPU"):
        TPUDevice()
    with pytest.raises(RuntimeError, match="needs a TPU"):
        TPUDevice(jax.devices()[0])
    assert XLADevice().platform == "cpu"


def test_second_tpu_worker_is_refused(monkeypatch, tmp_path):
    """One process for each chip: the shared spawn hook starts one
    worker on a TPU host and refuses the next while it lives."""
    from znicz_tpu.resilience import elastic

    monkeypatch.setattr(elastic, "_probe_backend", lambda _: ("tpu", 1))
    monkeypatch.setattr(elastic, "_tpu_workers", [])
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    sleeper = [sys.executable, "-c", "import time; time.sleep(60)"]

    def spawn(rank, env):
        return elastic.spawn_worker(sleeper, rank=rank, env=env,
                                    log_path=str(tmp_path / f"w{rank}"))

    first = spawn(0, env)
    try:
        with pytest.raises(elastic.ChipBusy, match="already holds"):
            spawn(1, env)
        # CPU-pinned workers are not the chip's business
        cpu = spawn(2, dict(env, JAX_PLATFORMS="cpu"))
        cpu.proc.kill()
        cpu.proc.wait(timeout=30)
    finally:
        first.proc.kill()
        first.proc.wait(timeout=30)
    again = spawn(3, env)                     # the chip is free again
    again.proc.kill()
    again.proc.wait(timeout=30)


# -- the phases, tiny ---------------------------------------------------------

def test_trainer_phase(trainer):
    w, losses, facts = trainer
    assert facts["train_steps"] == 2 and len(losses) == 2
    assert facts["compute_dtype"] == "float32"      # the CPU policy
    assert facts["cold_s"] > 0 and facts["steady_s_per_step"] > 0


def test_lm_trainer_phase_and_export(lm):
    w, losses, facts, pkg = lm
    assert facts["train_steps"] == 5
    assert facts["mosaic_kernels"] == []            # interpreted here
    assert facts["first_eval_loss"] is not None
    assert os.path.getsize(pkg) > 0


def test_lm_trainer_phase_demands_the_mosaic_kernels(corpus):
    """Without ``interpret`` the phase insists on finding both flash
    kernels in the lowered step — which a CPU lowering cannot have."""
    with pytest.raises(AssertionError, match="Mosaic kernels"):
        chip_smoke.run_lm_trainer(XLADevice(), data_dir=corpus, **LM)


def test_server_phase_tokens_identical_with_pallas_decode(lm):
    w, _, _, pkg = lm
    requests = chip_smoke.make_requests(int(w.loader.vocab_size), 32,
                                        n=4, max_tokens=4)
    assert len({len(r["tokens"]) for r in requests}) > 2    # mixed
    tokens = {}
    for pallas_decode in (False, True):
        tokens[pallas_decode], facts = chip_smoke.run_server(
            pkg, slots=2, max_len=32, requests=requests,
            pallas_decode=pallas_decode, boot_timeout_s=120)
        assert facts["requests"] == 4 and facts["pages_peak"] >= 2
    # f32 here: identical outright, nothing to adjudicate
    assert tokens[False] == tokens[True]
    verdict = chip_smoke.compare_greedy(pkg, requests, tokens[False],
                                        tokens[True], 32)
    assert verdict == {"requests_identical": 4, "requests": 4,
                       "near_ties": []}
    # a token the model is decided against is a wrong kernel, not a tie
    # (at vocab 10 the choice furthest behind is far outside the band)
    from znicz_tpu.serve.kvcache import KVDecoder
    from znicz_tpu.utils.export import load_lm

    params, meta = load_lm(pkg)
    _, logits = KVDecoder(params, heads=meta["heads"], max_len=32,
                          batch=1).prefill(requests[0]["tokens"])
    wrong = [list(t) for t in tokens[True]]
    wrong[0][0] = int(np.argmin(logits))
    with pytest.raises(AssertionError, match="NOT undecided"):
        chip_smoke.compare_greedy(pkg, requests, tokens[False], wrong, 32)


def test_kernels_phase_interpret():
    """The sweep the chip runs compiled must cover every kernel family
    and pass fully under the interpreter — so an on-chip failure can
    only be a lowering or hardware one."""
    results = chip_smoke.run_kernels(interpret=True)
    assert set(results) == {
        "sgd", "adam", "dropout", "lrn", "fc_gemm", "conv_fwd",
        "conv_bwd", "deconv", "stochastic_pool", "kohonen",
        "flash_attention", "conv_fwd_bf16", "flash_attention_bf16",
        "paged_decode", "sgd_bf16state"}


def test_kernels_phase_fails_on_anything_but_ok(monkeypatch):
    from znicz_tpu.utils import pallas_hw

    monkeypatch.setattr(pallas_hw, "run_parity",
                        lambda interpret: {"sgd": "ok",
                                           "adam": "FAIL: MosaicError"})
    with pytest.raises(AssertionError, match="adam"):
        chip_smoke.run_kernels(interpret=False)


def test_trainer_phase_with_engine_pallas(trainer):
    _, ref_losses, _ = trainer
    _, losses, facts = chip_smoke.run_trainer(XLADevice(), pallas=True,
                                              **TRAINER)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    # interpreted Pallas lowers to plain HLO: the count the chip run
    # insists on is zero here, and the prior flag value is restored
    assert facts["fused_sgd_update_calls"] == 0
    assert not root.common.engine.get("pallas", False)


def test_multichip_phase(trainer, lm, corpus, cpu_devices):
    _, t_losses, _ = trainer
    _, lm_losses, _, _ = lm
    facts = chip_smoke.run_multichip(
        cpu_devices[:4], trainer=TRAINER, lm=dict(LM, data_dir=corpus),
        ref_trainer_loss=t_losses[0],
        ref_lm_loss={c: v[0] for c, v in lm_losses.items()},
        interpret=True)
    assert facts["alexnet"]["devices"] == {"momenta": 4, "weights": 4,
                                           "batch": 4}
    assert facts["char_lm"]["devices"] == {"wq": 4, "emb": 4, "tokens": 4}
    assert facts["alexnet"]["all_reduces"] and \
        facts["char_lm"]["all_reduces"]
