"""Compile-only rehearsals at the real widths, for a v5e that is described
and not attached (on-chip-measurement guide, section 2): what the chip's
compiler refuses here costs no chip time.  Nothing runs, so these say
nothing about results or times.

The topology is described inside a module-scoped fixture, never at import,
and every test of the kind lives in this one file: only one process may
hold the TPU library.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import benchlib                                       # noqa: E402

CFG = benchlib.load_json(benchlib.HERE + "/configs/cerebras_gpt_1.3b.json")
TOKENS = benchlib.load_json(benchlib.HERE +
                            "/traffic/train_tokens_t2048.json")
HBM_USABLE = 15.75 * 2 ** 30      # what the runtime leaves of 16 GiB


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def test_flash_attention_fwd_and_bwd_compile_at_t2048_d2048(one_chip):
    import jax
    import jax.numpy as jnp

    from znicz_tpu.ops.pallas import attention as pattn

    b, t = int(TOKENS["minibatch_size"]), int(TOKENS["seq_len"])
    heads, d = int(CFG["n_head"]), int(CFG["n_embd"])
    assert pattn.unsupported_reason(t, d // heads) is None
    x = jax.ShapeDtypeStruct((b, t, heads, d // heads), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return pattn.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).compile() \
        .as_text()
    assert pattn.FWD_KERNEL_NAME in text and pattn.BWD_KERNEL_NAME in text


def test_gpt_train_step_fits_the_chip_at_the_depth_the_cell_runs(topo,
                                                                 monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.parallel.mesh import make_mesh

    # the step asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(tfm, "_flash_eligible", lambda mesh, interp: True)
    mesh = make_mesh({"data": 1, "seq": 1, "model": 1}, topo.devices[:1])
    opts = CFG["builders"]["lm_train"]
    layers = int(opts.get("n_layer", CFG["n_layer"]))
    d, ff, vocab = (int(CFG[k]) for k in ("n_embd", "n_inner", "vocab_size"))
    step, _ = tfm.make_train_step(
        mesh, layers, d, int(CFG["n_head"]), ff, vocab,
        lr=float(CFG["hyper"]["lr"]), masked=True,
        loss_chunks=opts["loss_chunks"])
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep),
        tfm.param_shapes(layers, d, ff, vocab),
        is_leaf=lambda x: isinstance(x, tuple))
    b, t = int(TOKENS["minibatch_size"]), int(TOKENS["seq_len"])
    tok = jax.ShapeDtypeStruct((b, t), jnp.int32,
                               sharding=NamedSharding(mesh, P("data", "seq")))
    mask = jax.ShapeDtypeStruct((b,), jnp.bool_,
                                sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(params, tok, tok, mask).compile()
    m = compiled.memory_analysis()
    # arguments + temporaries: the step does not donate, and the sum with
    # the outputs on top (17.0 GiB at 24 layers) overstates what the chip
    # then held (peak 10.65 GiB, my chip run, PR 23); this sum is 11.8
    live = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert live < HBM_USABLE, f"{live / 2 ** 30:.2f} GiB: {m}"
    assert "flash_attention_fwd" in compiled.as_text()


def test_decode_program_compiles_at_24_layers_16_slots(one_chip,
                                                       monkeypatch):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from znicz_tpu.parallel import transformer as tfm
    from znicz_tpu.serve.paged import PagedKVDecoder

    opts = CFG["builders"]["lm_serve"]
    d, ff, vocab = (int(CFG[k]) for k in ("n_embd", "n_inner", "vocab_size"))
    heads, layers = int(CFG["n_head"]), int(CFG["n_layer"])
    # a one-block stand-in of the real width gives the builder its
    # geometry; the program is then lowered for the real depth by shapes
    small = {"emb": np.zeros((8, d), np.float32),
             "head": np.zeros((d, 8), np.float32),
             "blocks": [{k: np.zeros(s, np.float32) for k, s in
                         tfm.param_shapes(1, d, ff, 8)["blocks"][0].items()}]}
    dec = PagedKVDecoder(small, heads=heads, max_len=int(opts["max_len"]),
                         batch=int(opts["slots"]), page=int(opts["page"]),
                         arena_pages=4)
    # both ask jax.default_backend(), which is the CPU here: on the chip
    # the programs compute in bfloat16 and donate the arena
    monkeypatch.setattr(dec, "_cast_policy", lambda: jnp.bfloat16)
    monkeypatch.setattr(type(dec), "_donate", property(lambda self: (1,)))
    view = dec.page_buckets[-1]
    fn = dec._build_pdecode(view)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: shaped(s, jnp.float32),
                          tfm.param_shapes(layers, d, ff, vocab),
                          is_leaf=lambda x: isinstance(x, tuple))
    arena = shaped((layers, int(opts["arena_pages"]), dec.page, heads,
                    d // heads), jnp.bfloat16)
    slots = dec.batch
    compiled = fn.lower(params, {"k": arena, "v": arena},
                        shaped((slots, view), jnp.int32),
                        shaped((slots,), jnp.int32),
                        shaped((slots,), jnp.int32)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes +
             m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_USABLE, f"{total / 2 ** 30:.2f} GiB: {m}"
