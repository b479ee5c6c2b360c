"""Service-layer tests: plotters, ImageSaver, web status, forward export +
forge (SURVEY.md §3.3 Graphics/Web/Forge rows, §4.5 inference path)."""

import json
import os
import urllib.request

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.backends import XLADevice
from znicz_tpu.core.memory import Array
from znicz_tpu.models import kohonen as kohonen_model, wine
from znicz_tpu.plotting import (AccumulatingPlotter, Histogram, ImagePlotter,
                                MatrixPlotter)
from znicz_tpu.units.image_saver import ImageSaver
from znicz_tpu.units.nn_plotting import (KohonenHits, KohonenInputMaps,
                                         KohonenNeighborMap, MultiHistogram,
                                         Weights2D, tile_filters)
from znicz_tpu.utils.export import (ExportedForward, export_forward,
                                    forge_fetch, forge_list, forge_publish)
from znicz_tpu.web_status import WebStatus


def _trained_wine(seed=3, **kw):
    prng.seed_all(seed)
    w = wine.build(max_epochs=3, n_train=60, n_valid=30, minibatch_size=10,
                   **kw)
    w.initialize(device=XLADevice())
    w.run()
    w.stop()
    return w


def test_plotters_render_files(tmp_path):
    w = _trained_wine()
    acc = AccumulatingPlotter(None, name="err_curve",
                              directory=str(tmp_path))
    for v in (5.0, 3.0, 1.0):
        acc.input = v
        acc.run()
    assert acc.render_count == 3 and os.path.exists(acc.last_path)

    mat = MatrixPlotter(None, name="confusion", directory=str(tmp_path))
    mat.input = np.array([[5, 1], [0, 7]])
    mat.run()
    assert os.path.exists(mat.last_path)

    img = ImagePlotter(None, name="sample", directory=str(tmp_path))
    img.input = np.zeros((8, 8, 1), np.float32)
    img.run()
    hist = Histogram(None, name="whist", directory=str(tmp_path))
    hist.input = w.forwards[0].weights
    hist.run()
    w2d = Weights2D(None, name="w2d", directory=str(tmp_path),
                    sample_shape=(13, 1))
    w2d.input = w.forwards[0].weights
    w2d.run()
    mh = MultiHistogram(None, name="mh", directory=str(tmp_path))
    mh.inputs = [f.weights for f in w.forwards]
    mh.run()
    assert len(os.listdir(tmp_path)) == 6


def test_tile_filters_shapes():
    grid = tile_filters(np.random.default_rng(0).normal(size=(16, 9))
                        .astype(np.float32))
    assert grid.shape == (3 * 5 - 1, 3 * 5 - 1)
    conv_grid = tile_filters(np.random.default_rng(0)
                             .normal(size=(3, 3, 2, 4)).astype(np.float32))
    assert conv_grid.shape == (2 * 4 - 1, 2 * 4 - 1)


def test_kohonen_plotters(tmp_path):
    prng.seed_all(23)
    w = kohonen_model.build(max_epochs=2, shape=(4, 4), n_train=200)
    w.initialize(device=XLADevice())
    w.run()
    w.forward.batch_size = 50
    w.forward.input = w.loader.minibatch_data
    w.forward.run()
    for cls, attr in ((KohonenHits, "forward"), (KohonenInputMaps, "trainer"),
                      (KohonenNeighborMap, "trainer")):
        p = cls(None, name=cls.__name__, directory=str(tmp_path))
        setattr(p, attr, getattr(w, attr))
        p.run()
        assert os.path.exists(p.last_path)


def test_image_saver(tmp_path):
    prng.seed_all(9)
    saver = ImageSaver(None, directory=str(tmp_path), limit=4)
    rng = np.random.default_rng(0)
    saver.input = Array(rng.normal(size=(10, 6, 6, 1)).astype(np.float32))
    probs = np.full((10, 3), 0.2, np.float32)
    probs[:, 0] = 0.6                      # predict class 0 for everyone
    saver.output = Array(probs)
    saver.labels = Array(np.arange(10, dtype=np.int32) % 3)
    saver.minibatch_size = 10
    saver.minibatch_class = 2
    saver.epoch_number = 1
    saver.run()
    saver.flush()
    assert 0 < len(saver.saved_paths) <= 4
    for p in saver.saved_paths:
        assert os.path.exists(p)


def test_web_status_endpoint():
    w = _trained_wine()
    ws = WebStatus(port=0).register(w)
    port = ws.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/status.json", timeout=5) as r:
            payload = json.loads(r.read())
        assert payload["workflows"][0]["name"] == "Wine"
        assert payload["workflows"][0]["complete"] is True
        assert len(payload["workflows"][0]["history"]) == 3
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=5) as r:
            assert b"Wine" in r.read()
    finally:
        ws.stop()


def test_export_and_forge_roundtrip(tmp_path):
    w = _trained_wine()
    pkg = str(tmp_path / "wine.npz")
    export_forward(w, pkg)
    model = ExportedForward(pkg)
    loader = w.loader
    data = loader.original_data.map_read()[:12]
    probs = model(data)
    assert probs.shape == (12, 3)
    # exported forward == the workflow's own eval forward (the fused chain
    # returns pre-softmax logits when the loss composes log_softmax)
    import jax
    w.step.sync_to_units()
    ref, logits_tail = w.step._forward_chain(w.step._params, data,
                                             train=False)
    assert logits_tail
    np.testing.assert_allclose(probs, np.asarray(jax.nn.softmax(ref, axis=1)),
                               rtol=1e-5, atol=1e-6)

    repo = str(tmp_path / "forge")
    forge_publish(pkg, repo, "wine", "1.0",
                  metrics={"best": w.decision.best_metric})
    forge_publish(pkg, repo, "wine", "1.1")
    assert forge_list(repo) == {"wine": ["1.0", "1.1"]}
    fetched = forge_fetch(repo, "wine")          # latest
    np.testing.assert_allclose(fetched(data), probs, rtol=1e-6)


# -- forge registry (SURVEY §3.3) --------------------------------------------

def test_forge_upload_fetch_roundtrip(tmp_path):
    import numpy as np
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.models import wine
    from znicz_tpu.utils.export import ExportedForward
    from znicz_tpu.utils.forge import ForgeRegistry

    prng.seed_all(3)
    w = wine.build(max_epochs=2, n_train=60, n_valid=30, minibatch_size=10)
    w.initialize(device=XLADevice())
    w.run()
    w.stop()

    reg = ForgeRegistry(str(tmp_path / "registry"))
    entry = reg.upload_workflow(w, "wine", "1.0")
    assert entry["metadata"]["workflow"] == "WineDemo" or \
        entry["metadata"]["workflow"] == w.name
    assert reg.list_packages() == {"wine": ["1.0"]}
    # immutability
    import pytest
    with pytest.raises(FileExistsError):
        reg.upload_workflow(w, "wine", "1.0")
    reg.upload_workflow(w, "wine", "1.1")
    # latest fetch + checksum + inference parity with a direct export
    from znicz_tpu.utils.export import export_forward
    direct = str(tmp_path / "direct.npz")
    export_forward(w, direct)
    dest = reg.fetch("wine", dest=str(tmp_path / "got.npz"))
    loaded = ExportedForward(dest)
    x = np.asarray(w.loader.original_data.map_read()[:8], np.float32)
    np.testing.assert_allclose(loaded(x), ExportedForward(direct)(x),
                               rtol=1e-6)
    # in-place fetch serves the registry file itself (no copy)
    in_place = reg.fetch("wine")
    assert in_place.startswith(str(tmp_path / "registry"))
    with pytest.raises(KeyError):
        reg.fetch("nonexistent")
    with pytest.raises(KeyError):
        reg.fetch("wine", "9.9")


def test_forge_detects_corruption(tmp_path):
    import numpy as np
    from znicz_tpu.utils.forge import ForgeRegistry

    pkg = tmp_path / "pkg.npz"
    np.savez(pkg, a=np.arange(3))
    reg = ForgeRegistry(str(tmp_path / "reg"))
    reg.upload(str(pkg), "thing", "0.1")
    # corrupt the stored file
    stored = tmp_path / "reg" / "thing-0.1.npz"
    stored.write_bytes(b"corrupted")
    import pytest
    with pytest.raises(IOError, match="sha256"):
        reg.fetch("thing", dest=str(tmp_path / "out.npz"))


def test_launcher_profile_trace(tmp_path):
    from znicz_tpu.core import prng
    from znicz_tpu.core.backends import XLADevice
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.models import wine

    prng.seed_all(3)
    launcher = Launcher(device=XLADevice(),
                        profile_dir=str(tmp_path / "trace"))
    launcher.load(wine.build, max_epochs=1, n_train=60, n_valid=30,
                  minibatch_size=10)
    launcher.main()
    import os
    found = []
    for base, _dirs, files in os.walk(tmp_path / "trace"):
        found += files
    assert found, "no profiler trace files written"


def test_trace_summary_reports_top_ops(tmp_path):
    """summarize_trace turns a jax.profiler dump into a top-ops table
    (CPU traces summarize the host plane with python frames dropped)."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.utils.profiling import format_summary, summarize_trace

    d = str(tmp_path / "trace")
    with jax.profiler.trace(d):
        x = jnp.ones((128, 128))
        for _ in range(3):
            x = jnp.tanh(x @ x)
        jax.block_until_ready(x)
    rows = summarize_trace(d, top=10)
    assert rows and all(r["total_ms"] >= 0 for r in rows)
    assert not any(r["op"].startswith("$") for r in rows)
    text = format_summary(rows)
    assert "total_ms" in text and len(text.splitlines()) == len(rows) + 1


def test_manhole_repl_session():
    """Live-REPL service (the reference's manhole): expressions echo
    their repr, statements exec with stdout captured, errors return a
    traceback without killing the session.  The socket is AF_UNIX with
    0600 permissions — other local uids must not reach the exec REPL."""
    import os
    import socket
    import stat
    import time

    from znicz_tpu.utils.manhole import Manhole

    hole = Manhole(namespace={"answer": 41})
    path = hole.start()
    try:
        mode = os.stat(path).st_mode
        assert stat.S_ISSOCK(mode)
        assert stat.S_IMODE(mode) == 0o600            # owner-only
        assert stat.S_IMODE(os.stat(os.path.dirname(path)).st_mode) == 0o700
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.settimeout(5)
        conn.connect(path)
        for line in ("answer + 1", "x = answer * 2", "print(x)", "1/0"):
            conn.sendall(line.encode() + b"\n")
        time.sleep(0.5)
        out = conn.recv(65536).decode()
        assert "manhole" in out                       # banner
        assert "42" in out                            # expression repr
        assert "82" in out                            # statement stdout
        assert "ZeroDivisionError" in out             # traceback, not death
        conn.sendall(b"answer\n")                     # session survived
        time.sleep(0.3)
        assert "41" in conn.recv(65536).decode()
        conn.close()
    finally:
        hole.stop()
    # teardown: listener closed, serving thread exited, socket unlinked
    assert hole._sock.fileno() == -1
    assert not hole._thread.is_alive()
    assert not os.path.exists(path)


def test_launcher_serves_manhole():
    """Launcher with manhole_path="" (auto private socket) serves the
    live workflow namespace during the run and tears it down after."""
    import socket
    import time

    from znicz_tpu.launcher import Launcher
    from znicz_tpu.models import wine

    prng.seed_all(3)
    launcher = Launcher(device=XLADevice(), manhole_path="")
    launcher.load(wine.build, max_epochs=1, n_train=60, n_valid=30,
                  minibatch_size=10)

    # probe the manhole DURING the run, from the decision's epoch hook
    seen = {}
    wf = launcher.workflow
    orig_run = wf.decision.run

    def probing_run():
        orig_run()
        if launcher.manhole is not None and "reply" not in seen:
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.settimeout(5)
            conn.connect(launcher.manhole.path)
            conn.sendall(b"wf.name\n")
            time.sleep(0.3)
            seen["reply"] = conn.recv(65536).decode()
            conn.close()

    wf.decision.run = probing_run
    launcher.main()
    assert "Wine" in seen.get("reply", ""), seen
    assert launcher.manhole._sock.fileno() == -1      # torn down
