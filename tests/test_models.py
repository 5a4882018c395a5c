"""Model-family smoke/learning tests (tiny real computations on CPU —
the reference's test trick, SURVEY.md §4: no mocked math, just small real
models)."""

import numpy as np
import pytest

import jax
import jax.flatten_util
import jax.numpy as jnp


class TestMNIST:
  def test_mlp_learns(self):
    from tensorflowonspark_tpu.models import mnist
    images, labels = mnist.synthetic_dataset(256, seed=1)
    state = mnist.create_state(jax.random.PRNGKey(0))
    first = last = None
    for step in range(20):
      state, loss = mnist.train_step(state, images[:64], labels[:64])
      first = float(loss) if first is None else first
      last = float(loss)
    assert last < first * 0.5

  def test_cnn_shapes(self):
    from tensorflowonspark_tpu.models import mnist
    state = mnist.create_state(jax.random.PRNGKey(0), model=mnist.CNN())
    images, labels = mnist.synthetic_dataset(8)
    state, loss = mnist.train_step(state, images, labels)
    assert np.isfinite(float(loss))

  def test_eval_accuracy_on_learnable_data(self):
    from tensorflowonspark_tpu.models import mnist
    images, labels = mnist.synthetic_dataset(128, seed=2)
    state = mnist.create_state(jax.random.PRNGKey(0))
    for _ in range(30):
      state, _ = mnist.train_step(state, images, labels)
    _, acc = mnist.eval_step(state, images, labels)
    assert float(acc) > 0.9


class TestResNet:
  def test_resnet56_cifar_step(self):
    from tensorflowonspark_tpu.models import resnet
    model = resnet.ResNet56CIFAR()
    state = resnet.create_state(jax.random.PRNGKey(0), model,
                                image_shape=(32, 32, 3),
                                learning_rate=0.01)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.rand(8, 32, 32, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 10, 8), jnp.int32)
    state, loss = resnet.train_step(state, images, labels)
    assert np.isfinite(float(loss))
    # batch stats must have been updated by the step
    stem_mean = state.batch_stats["stem_bn"]["mean"]
    assert float(jnp.abs(stem_mean).sum()) > 0

  @pytest.mark.slow
  def test_resnet50_forward_shape(self):
    # Marked slow (tier-1 budget audit): ~15 s to build/init ResNet-50
    # for a shape-only assertion; test_resnet56_cifar_step trains a
    # real residual net in tier-1. Runs via `make test`.
    from tensorflowonspark_tpu.models import resnet
    model = resnet.ResNet50(num_classes=1000)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 64, 64, 3)), train=False)
    logits = model.apply(variables, jnp.zeros((2, 64, 64, 3)), train=False)
    assert logits.shape == (2, 1000)


class TestSegmentation:
  def test_unet_learns_circles(self):
    from tensorflowonspark_tpu.models import segmentation as seg
    images, masks = seg.synthetic_dataset(16, size=64, seed=0)
    state = seg.create_state(jax.random.PRNGKey(0),
                             model=seg.UNet(encoder_filters=(8, 16)),
                             image_shape=(64, 64, 3))
    first = last = None
    for _ in range(10):
      state, loss = seg.train_step(state, jnp.asarray(images),
                                   jnp.asarray(masks))
      first = float(loss) if first is None else first
      last = float(loss)
    assert last < first


@pytest.mark.parametrize("switch,kernels", [
    ("fuse_qkv", ()),
    ("ln_matmul_impl", ("ln_matmul", "ln_matmul_sharded")),
    ("act_matmul_impl", ("gelu_matmul", "gelu_matmul_sharded")),
])
def test_a_retired_fusion_switch_is_gone(switch, kernels):
  """PR 43 judged the three fusion switches on the chip and none beat the
  unfused block (PERF.md section 6): each went whole, option and kernel,
  and none comes back as an alias or a field nobody reads."""
  import dataclasses
  from tensorflowonspark_tpu import ops
  from tensorflowonspark_tpu.models import transformer as tfm
  assert switch not in {f.name for f in dataclasses.fields(
      tfm.TransformerConfig)}
  with pytest.raises(TypeError, match=switch):
    tfm.TransformerConfig(**{switch: "fused"})
  assert not [k for k in kernels if hasattr(ops, k)]


class TestTransformer:
  def test_remat_policy_numerics_invariant(self):
    """remat is a memory/compute trade, never a numerics one: loss and
    grads agree across remat off / full recompute / dots-saveable
    (selective) policies at identical params."""
    import dataclasses
    from tensorflowonspark_tpu.models import transformer as tfm
    base = tfm.TransformerConfig(vocab_size=32, num_layers=2, num_heads=2,
                                 d_model=32, d_ff=64, max_seq_len=16,
                                 remat=False, dtype=jnp.float32)
    state = tfm.create_state(jax.random.PRNGKey(0), base, seq_len=16)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (2, 16)), jnp.int32)

    def lossgrad(cfg):
      def loss(p):
        return tfm.causal_lm_loss(
            tfm.Transformer(cfg, None).apply({"params": p}, tokens),
            tokens)
      return jax.value_and_grad(loss)(state.params)

    l0, g0 = lossgrad(base)
    for policy in ("none", "dots"):
      cfg = dataclasses.replace(base, remat=True, remat_policy=policy)
      l1, g1 = lossgrad(cfg)
      np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
      f0, _ = jax.flatten_util.ravel_pytree(g0)
      f1, _ = jax.flatten_util.ravel_pytree(g1)
      np.testing.assert_allclose(np.asarray(f0), np.asarray(f1),
                                 atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="remat_policy"):
      tfm.TransformerConfig(remat_policy="everything")

  def test_greedy_generate_learns_cycle(self):
    """Train on a repeating token cycle; generation must continue it."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=2, num_heads=2,
                                d_model=64, d_ff=128, remat=False)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg,
                             learning_rate=3e-3, seq_len=24)
    cycle = np.tile(np.arange(8), 10)
    tokens = jnp.asarray(np.stack([cycle[i:i + 24] for i in range(8)]),
                         jnp.int32)

    @jax.jit
    def step(state, tokens):
      def loss_fn(p):
        return tfm.causal_lm_loss(
            state.apply_fn({"params": p}, tokens), tokens)
      loss, grads = jax.value_and_grad(loss_fn)(state.params)
      return state.apply_gradients(grads=grads), loss

    for _ in range(150):
      state, loss = step(state, tokens)
    assert float(loss) < 0.1, float(loss)

    prompt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    out = tfm.greedy_generate(state.params, cfg, prompt, num_steps=8)
    generated = np.asarray(out[0, 4:])
    np.testing.assert_array_equal(generated,
                                  [4, 5, 6, 7, 0, 1, 2, 3])

  def test_kv_cache_generate_matches_recompute(self):
    """The KV-cache decode path must agree with full-recompute decoding:
    logits numerically close on the prefill, token streams identical on a
    trained (decisive-logits) model."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=2, num_heads=2,
                                d_model=64, d_ff=128, max_seq_len=32,
                                remat=False, dtype=jnp.float32)
    state = tfm.create_state(jax.random.PRNGKey(3), cfg,
                             learning_rate=3e-3, seq_len=24)

    # prefill logits: decode path vs normal forward
    model = tfm.Transformer(cfg)
    prompt = jnp.asarray([[5, 9, 2, 11], [1, 1, 7, 0]], jnp.int32)
    ref_logits = model.apply({"params": state.params}, prompt)
    cache = jax.tree.map(
        jnp.zeros_like,
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
                   decode=True)["cache"])
    kv_logits, _ = model.apply({"params": state.params, "cache": cache},
                               prompt, decode=True, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(kv_logits),
                               np.asarray(ref_logits), atol=1e-4,
                               rtol=1e-4)

    # train until the model is decisive, then token streams must be equal
    cycle = np.tile(np.arange(8), 10)
    tokens = jnp.asarray(np.stack([cycle[i:i + 24] for i in range(8)]),
                         jnp.int32)

    @jax.jit
    def step(state, tokens):
      def loss_fn(p):
        return tfm.causal_lm_loss(
            state.apply_fn({"params": p}, tokens), tokens)
      loss, grads = jax.value_and_grad(loss_fn)(state.params)
      return state.apply_gradients(grads=grads), loss

    for _ in range(150):
      state, _ = step(state, tokens)
    prompt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    full = tfm.greedy_generate(state.params, cfg, prompt, num_steps=10)
    kv = tfm.greedy_generate_kv(state.params, cfg, prompt, num_steps=10)
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(full))

  def test_eos_early_stop_matches_plain_decode(self):
    """greedy_generate_kv(eos_id=...) agrees with the eos-free decode up
    to (and including) each row's stop position; every later position is
    the pad id — the per-sequence-stop satellite, and the primitive the
    serving engine's slot-free logic reuses."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=2, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=32,
                                remat=False, dtype=jnp.float32)
    state = tfm.create_state(jax.random.PRNGKey(5), cfg, seq_len=16)
    prompt = jnp.asarray([[5, 9, 2, 11], [1, 1, 7, 0], [3, 3, 3, 3]],
                         jnp.int32)
    steps, pad = 12, 15
    plain = np.asarray(tfm.greedy_generate_kv(state.params, cfg, prompt,
                                              steps))
    # pick an eos that actually fires for at least one row mid-stream
    gen = plain[:, 4:]
    eos = int(gen[0, steps // 2])
    assert eos != pad
    out = np.asarray(tfm.greedy_generate_kv(state.params, cfg, prompt,
                                            steps, eos_id=eos, pad_id=pad))
    fired = 0
    for row in range(prompt.shape[0]):
      stops = np.where(gen[row] == eos)[0]
      stop = (int(stops[0]) + 1) if len(stops) else steps
      np.testing.assert_array_equal(out[row, :4 + stop],
                                    plain[row, :4 + stop])
      assert (out[row, 4 + stop:] == pad).all(), (row, out[row])
      fired += bool(len(stops))
    assert fired >= 1, "chosen eos never fired; test proves nothing"

  def test_eos_pad_collision_rejected(self):
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=1, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=16,
                                remat=False)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=8)
    with pytest.raises(ValueError, match="eos_id and pad_id"):
      tfm.greedy_generate_kv(state.params, cfg,
                             jnp.asarray([[1, 2]], jnp.int32), 4,
                             eos_id=0, pad_id=0)

  def test_chunked_prefill_into_warm_cache_matches(self):
    """The idx > 0 chunked-prefill decode path: pushing a prompt through
    the cache in two apply calls (fresh-cache chunk, then a warm-cache
    insert) produces the same last-position logits and the same
    subsequent greedy stream as one whole-prompt prefill."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=2, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=32,
                                remat=False, dtype=jnp.float32)
    state = tfm.create_state(jax.random.PRNGKey(3), cfg, seq_len=16)
    model = tfm.Transformer(cfg)
    prompt = jnp.asarray([[5, 9, 2, 11, 4, 1, 8, 14, 2, 6, 0, 12]],
                         jnp.int32)

    whole, _ = model.apply(
        {"params": state.params, "cache": tfm._zero_cache(model, 1)},
        prompt, decode=True, mutable=["cache"])
    l1, mut = model.apply(
        {"params": state.params, "cache": tfm._zero_cache(model, 1)},
        prompt[:, :8], decode=True, mutable=["cache"])
    l2, mut = model.apply({"params": state.params, "cache": mut["cache"]},
                          prompt[:, 8:], decode=True, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(l2[:, -1]),
                               np.asarray(whole[:, -1]),
                               atol=1e-4, rtol=1e-4)
    # greedy continuation from the chunk-filled cache matches the
    # single-prefill serving decode stream
    cache, toks = mut["cache"], []
    tok = jnp.argmax(l2[:, -1], -1).astype(jnp.int32)
    toks.append(int(tok[0]))
    for _ in range(5):
      lg, mut = model.apply({"params": state.params, "cache": cache},
                            tok[:, None], decode=True, mutable=["cache"])
      cache = mut["cache"]
      tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
      toks.append(int(tok[0]))
    ref = np.asarray(tfm.greedy_generate_kv(state.params, cfg, prompt,
                                            6))[0, prompt.shape[1]:]
    np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)

  def test_moe_transformer_learns(self):
    """MoE layers inside the flagship model: trains, and the aux loss is
    exposed through intermediates."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32, num_layers=2, num_heads=2,
                                d_model=32, d_ff=64, remat=False,
                                dtype=jnp.float32, moe_experts=4,
                                moe_top_k=2, moe_every=2)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg,
                             learning_rate=3e-3, seq_len=16)
    assert "moe" in state.params["layer_1"]      # layer 1 is the MoE layer
    assert "mlp" in state.params["layer_0"]
    tokens = jnp.asarray(np.tile(np.arange(16) % 8, (4, 1)), jnp.int32)

    @jax.jit
    def step(state, tokens):
      def loss_fn(p):
        logits, inter = state.apply_fn(
            {"params": p}, tokens, mutable=["intermediates"])
        aux = sum(jax.tree.leaves(inter["intermediates"]))
        return tfm.causal_lm_loss(logits, tokens) + 0.01 * aux
      loss, grads = jax.value_and_grad(loss_fn)(state.params)
      return state.apply_gradients(grads=grads), loss

    losses = []
    for _ in range(30):
      state, loss = step(state, tokens)
      losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses[:3] + losses[-3:]

  def test_sampling_generation(self):
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=32,
                                remat=False, dtype=jnp.float32)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=8)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    a = tfm.greedy_generate_kv(state.params, cfg, prompt, 10,
                               temperature=1.0, top_k=5,
                               rng=jax.random.PRNGKey(1))
    b = tfm.greedy_generate_kv(state.params, cfg, prompt, 10,
                               temperature=1.0, top_k=5,
                               rng=jax.random.PRNGKey(2))
    assert a.shape == (1, 13)
    # different rng -> (almost surely) different samples; same rng -> same
    c = tfm.greedy_generate_kv(state.params, cfg, prompt, 10,
                               temperature=1.0, top_k=5,
                               rng=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert not np.array_equal(np.asarray(a), np.asarray(b))

  def test_sharded_decode_matches_single_device(self):
    """Tensor-parallel KV-cache decode (heads + cache over the `tensor`
    axis, batch over `data`) produces token-for-token the single-device
    result — the multi-chip serving path (reference TFModel.scala:245-292
    scaled past one chip, round-4 verdict item 4)."""
    from tensorflowonspark_tpu.models import transformer as tfm
    from tensorflowonspark_tpu.parallel import mesh as mesh_lib
    cfg = tfm.TransformerConfig(vocab_size=128, num_layers=2, num_heads=4,
                                num_kv_heads=2, d_model=64, d_ff=128,
                                max_seq_len=32, remat=False,
                                dtype=jnp.float32)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (4, 8)), jnp.int32)
    ref = tfm.greedy_generate_kv(state.params, cfg, prompt, 6)
    mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(data=-1, tensor=2))
    out = tfm.greedy_generate_kv(state.params, cfg, prompt, 6, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
    # a RAGGED batch (3 rows on a data=4 mesh — pipeline.yield_batch's
    # final-batch shape) pads through and slices back, matching row-wise
    out3 = tfm.greedy_generate_kv(state.params, cfg, prompt[:3], 6,
                                  mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref)[:3], np.asarray(out3))
    # forced-flash + long prompt on the mesh: the prefill kernel runs
    # shard_mapped (heads over tensor, batch over data) and must match
    # the dense meshed decode token-for-token at this logit scale
    cfg_f = tfm.TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        d_model=64, d_ff=128, max_seq_len=160, remat=False,
        dtype=jnp.float32, attention_impl="flash")
    cfg_d = tfm.TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        d_model=64, d_ff=128, max_seq_len=160, remat=False,
        dtype=jnp.float32, attention_impl="dense")
    state_l = tfm.create_state(jax.random.PRNGKey(1), cfg_d, seq_len=16)
    long_prompt = jnp.asarray(
        np.random.RandomState(5).randint(0, 128, (4, 128)), jnp.int32)

    def meshed_prefill_logits(cfg):
      model = tfm.Transformer(cfg, mesh=mesh)
      cache = jax.tree.map(
          jnp.zeros_like,
          model.init(jax.random.PRNGKey(0), jnp.zeros((4, 1), jnp.int32),
                     decode=True)["cache"])
      logits, _ = model.apply(
          {"params": state_l.params, "cache": cache}, long_prompt,
          decode=True, mutable=["cache"])
      return np.asarray(logits)

    # logits, not tokens: blockwise softmax reorders sums (near-tied
    # argmax flips would make token equality environment-fragile)
    np.testing.assert_allclose(meshed_prefill_logits(cfg_f),
                               meshed_prefill_logits(cfg_d),
                               atol=1e-4, rtol=1e-4)

  # plen=128 marked slow (tier-1 budget audit): same kernel path at a
  # second block multiple — the 64 leg keeps the contract tier-1-pinned,
  # 128 runs via `make test`.
  @pytest.mark.parametrize(
      "plen", [64, pytest.param(128, marks=pytest.mark.slow)])
  def test_flash_prefill_matches_dense_decode(self, plen):
    """The serving prefill fast path is a pure substitution: the prefill
    LOGITS through the GQA flash kernel (forced flash = interpret mode on
    CPU) match the dense cache path within numerics (blockwise online
    softmax reorders the sums, so exact token equality would be an
    environment-fragile assertion on near-tied logits). plen=64 also pins
    that forcing flash engages below 128 — _flash_eligible's own
    divisibility rule decides, not a duplicated block constant."""
    from tensorflowonspark_tpu.models import transformer as tfm
    base = dict(vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
                d_model=32, d_ff=64, max_seq_len=160, remat=False,
                dtype=jnp.float32)
    cfg_flash = tfm.TransformerConfig(attention_impl="flash", **base)
    cfg_dense = tfm.TransformerConfig(attention_impl="dense", **base)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg_dense, seq_len=16)
    prompt = jnp.asarray(
        np.random.RandomState(3).randint(0, 64, (2, plen)), jnp.int32)

    def prefill_logits(cfg):
      model = tfm.Transformer(cfg)
      cache = jax.tree.map(
          jnp.zeros_like,
          model.init(jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
                     decode=True)["cache"])
      logits, _ = model.apply({"params": state.params, "cache": cache},
                              prompt, decode=True, mutable=["cache"])
      return np.asarray(logits)

    np.testing.assert_allclose(prefill_logits(cfg_flash),
                               prefill_logits(cfg_dense),
                               atol=1e-4, rtol=1e-4)

  def test_speculative_decode_exactly_greedy(self):
    """Greedy speculative decoding is LOSSLESS: whatever the draft
    proposes, the emitted tokens are exactly the target's own greedy
    decode — checked with (a) the target as its own draft (full
    acceptance every round) and (b) an unrelated random draft (mostly
    rejections, exercising the bonus-token and rollback paths)."""
    from tensorflowonspark_tpu.models import transformer as tfm
    base = dict(vocab_size=32, num_layers=2, num_heads=2, d_model=32,
                d_ff=64, max_seq_len=64, remat=False, dtype=jnp.float32)
    cfg = tfm.TransformerConfig(**base)
    dcfg = tfm.TransformerConfig(**{**base, "num_layers": 1})
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=8)
    draft_other = tfm.create_state(jax.random.PRNGKey(9), dcfg, seq_len=8)
    prompt = jnp.asarray(
        np.random.RandomState(2).randint(0, 32, (2, 8)), jnp.int32)
    ref = np.asarray(tfm.greedy_generate_kv(state.params, cfg, prompt, 12))

    self_spec = tfm.speculative_generate_kv(
        state.params, cfg, state.params, cfg, prompt, 12, draft_k=4)
    np.testing.assert_array_equal(np.asarray(self_spec), ref)

    cross_spec = tfm.speculative_generate_kv(
        draft_other.params, dcfg, state.params, cfg, prompt, 12,
        draft_k=3)
    np.testing.assert_array_equal(np.asarray(cross_spec), ref)

    # composes with the int8 cache: exactness is vs the int8-cache
    # greedy (quantization shifts logits identically in both paths)
    cfg8 = tfm.TransformerConfig(kv_cache_dtype="int8", **base)
    ref8 = np.asarray(
        tfm.greedy_generate_kv(state.params, cfg8, prompt, 12))
    spec8 = tfm.speculative_generate_kv(
        draft_other.params, dcfg, state.params, cfg8, prompt, 12,
        draft_k=3)
    np.testing.assert_array_equal(np.asarray(spec8), ref8)

  def test_int8_kv_cache_close_and_compact(self):
    """kv_cache_dtype='int8': the cache leaves really are int8 (the
    serving-memory/HBM claim), decode runs end-to-end, and prefill logits
    stay within the ~0.4%-per-entry quantization envelope of the
    full-precision cache."""
    from tensorflowonspark_tpu.models import transformer as tfm
    base = dict(vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
                d_model=32, d_ff=64, max_seq_len=64, remat=False,
                dtype=jnp.float32)
    cfg8 = tfm.TransformerConfig(kv_cache_dtype="int8", **base)
    cfgm = tfm.TransformerConfig(**base)
    state = tfm.create_state(jax.random.PRNGKey(0), cfgm, seq_len=16)
    prompt = jnp.asarray(
        np.random.RandomState(7).randint(0, 64, (2, 16)), jnp.int32)

    cache8 = tfm.Transformer(cfg8).init(
        jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
        decode=True)["cache"]
    dtypes = {np.dtype(leaf.dtype) for leaf in jax.tree.leaves(cache8)}
    assert np.dtype(np.int8) in dtypes       # quantized values
    assert np.dtype(np.float32) in dtypes    # scales

    def prefill_logits(cfg):
      model = tfm.Transformer(cfg)
      cache = jax.tree.map(
          jnp.zeros_like,
          model.init(jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
                     decode=True)["cache"])
      logits, _ = model.apply({"params": state.params, "cache": cache},
                              prompt, decode=True, mutable=["cache"])
      return np.asarray(logits)

    np.testing.assert_allclose(prefill_logits(cfg8), prefill_logits(cfgm),
                               atol=0.15, rtol=0.15)
    out = tfm.greedy_generate_kv(state.params, cfg8, prompt, 6)
    assert out.shape == (2, 22)

  def test_kv_cache_respects_max_len(self):
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=8, num_layers=1, num_heads=2,
                                d_model=16, d_ff=32, max_seq_len=8,
                                remat=False)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=4)
    with pytest.raises(ValueError, match="max_seq_len"):
      tfm.greedy_generate_kv(state.params, cfg,
                             jnp.zeros((1, 4), jnp.int32), num_steps=8)

  def test_single_device_learns(self):
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                                d_model=32, d_ff=64, remat=False)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg,
                             learning_rate=1e-2, seq_len=16)
    tokens = jnp.asarray(np.tile(np.arange(16) % 8, (4, 1)), jnp.int32)

    @jax.jit
    def step(state, tokens):
      def loss_fn(p):
        return tfm.causal_lm_loss(
            state.apply_fn({"params": p}, tokens), tokens)
      loss, grads = jax.value_and_grad(loss_fn)(state.params)
      return state.apply_gradients(grads=grads), loss

    losses = [None]
    for _ in range(10):
      state, loss = step(state, tokens)
      losses.append(float(loss))
    assert losses[-1] < losses[1] * 0.8

  def test_forced_flash_matches_dense_in_model(self):
    """attention_impl="flash" trains the model through the Pallas kernels
    (interpret mode off-TPU) on the same trajectory as dense attention —
    the production attention path exercised by CPU CI."""
    from tensorflowonspark_tpu.models import transformer as tfm

    tokens = jnp.asarray(np.tile(np.arange(32) % 8, (4, 1)), jnp.int32)
    losses = {}
    for impl in ("dense", "flash"):
      cfg = tfm.TransformerConfig(vocab_size=32, num_layers=2, num_heads=2,
                                  d_model=32, d_ff=64, max_seq_len=32,
                                  remat=False, dtype=jnp.float32,
                                  attention_impl=impl)
      state = tfm.create_state(jax.random.PRNGKey(0), cfg,
                               learning_rate=1e-2, seq_len=32)

      @jax.jit
      def step(state, tokens):
        def loss_fn(p):
          return tfm.causal_lm_loss(
              state.apply_fn({"params": p}, tokens), tokens)
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

      traj = []
      for _ in range(4):
        state, loss = step(state, tokens)
        traj.append(float(loss))
      losses[impl] = traj
    np.testing.assert_allclose(losses["flash"], losses["dense"],
                               atol=2e-4, rtol=2e-4)

  def test_forced_flash_rejects_indivisible_seq(self):
    """attention_impl='flash' must fail loudly, never silently fall back
    to dense, when the sequence doesn't divide into kernel blocks."""
    import pytest
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=192,
                                remat=False, attention_impl="flash")
    with pytest.raises(ValueError, match="divide into kernel blocks"):
      tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=192)

  def test_forced_flash_model_still_generates_unaligned_lengths(self):
    """greedy_generate's buffer (plen + num_steps) is an internal shape —
    a forced-flash model must generate at any length (the generate path
    degrades to auto/dense for unaligned buffers instead of raising)."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32, num_layers=1, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=256,
                                remat=False, attention_impl="flash")
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=128)
    prompt = jnp.zeros((1, 2), jnp.int32)
    out = tfm.greedy_generate(state.params, cfg, prompt, num_steps=131)
    assert out.shape == (1, 133)          # 133 % 128 != 0: dense fallback

  def test_config_rejects_unknown_impls(self):
    import pytest
    from tensorflowonspark_tpu.models import transformer as tfm
    with pytest.raises(ValueError, match="attention_impl"):
      tfm.TransformerConfig(attention_impl="Flash")
    with pytest.raises(ValueError, match="layer_norm_impl"):
      tfm.TransformerConfig(layer_norm_impl="pallas")

  def test_gqa_config_validation(self):
    import pytest
    from tensorflowonspark_tpu.models import transformer as tfm
    with pytest.raises(ValueError, match="num_kv_heads"):
      tfm.TransformerConfig(num_heads=12, num_kv_heads=5)
    assert tfm.TransformerConfig(num_heads=12, num_kv_heads=4).kv_heads == 4
    assert tfm.TransformerConfig(num_heads=12).kv_heads == 12

  def test_gqa_cache_holds_only_kv_heads(self):
    """Under GQA the per-layer KV cache stores kv_heads heads — the
    num_heads/num_kv_heads serving-memory reduction is the point."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=2, num_heads=4,
                                num_kv_heads=2, d_model=64, d_ff=128,
                                max_seq_len=32, remat=False,
                                dtype=jnp.float32)
    model = tfm.Transformer(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 1), jnp.int32), decode=True)
    # [batch, max_seq_len, kv_heads * head_dim]: heads folded into the
    # minor axis (the layout decode attention computes on)
    kv_arrays = [leaf for leaf in jax.tree.leaves(variables["cache"])
                 if getattr(leaf, "ndim", 0) == 3]
    assert kv_arrays, "no KV cache arrays found"
    for leaf in kv_arrays:
      assert leaf.shape == (1, 32, 2 * cfg.head_dim), leaf.shape

  def test_gqa_kv_cache_matches_recompute(self):
    """GQA decode through the grouped-einsum cache path must agree with
    the full-recompute forward (which expands KV heads per group)."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=2, num_heads=4,
                                num_kv_heads=1, d_model=64, d_ff=128,
                                max_seq_len=32, remat=False,
                                dtype=jnp.float32)
    state = tfm.create_state(jax.random.PRNGKey(3), cfg,
                             learning_rate=3e-3, seq_len=24)
    model = tfm.Transformer(cfg)
    prompt = jnp.asarray([[5, 9, 2, 11], [1, 1, 7, 0]], jnp.int32)
    ref_logits = model.apply({"params": state.params}, prompt)
    cache = jax.tree.map(
        jnp.zeros_like,
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
                   decode=True)["cache"])
    kv_logits, _ = model.apply({"params": state.params, "cache": cache},
                               prompt, decode=True, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(kv_logits),
                               np.asarray(ref_logits), atol=1e-4,
                               rtol=1e-4)

  def test_gqa_learns_and_generates(self):
    """A grouped-KV model trains to a decisive solution and the KV-cache
    token stream equals full recompute."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=2, num_heads=4,
                                num_kv_heads=2, d_model=64, d_ff=128,
                                max_seq_len=32, remat=False)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg,
                             learning_rate=3e-3, seq_len=24)
    cycle = np.tile(np.arange(8), 10)
    tokens = jnp.asarray(np.stack([cycle[i:i + 24] for i in range(8)]),
                         jnp.int32)

    @jax.jit
    def step(state, tokens):
      def loss_fn(p):
        return tfm.causal_lm_loss(
            state.apply_fn({"params": p}, tokens), tokens)
      loss, grads = jax.value_and_grad(loss_fn)(state.params)
      return state.apply_gradients(grads=grads), loss

    for _ in range(150):
      state, loss = step(state, tokens)
    assert float(loss) < 0.1, float(loss)
    prompt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    full = tfm.greedy_generate(state.params, cfg, prompt, num_steps=8)
    kv = tfm.greedy_generate_kv(state.params, cfg, prompt, num_steps=8)
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(full))

  def test_blocked_loss_matches_full(self):
    """causal_lm_loss_blocked (fused projection+xent, [B,chunk,V] peak
    memory) matches causal_lm_loss exactly in f32, including value AND
    gradients, at a sequence length that doesn't divide the chunk."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=97, num_layers=2, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=50,
                                dtype=jnp.float32)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=50)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 97, (3, 50)), jnp.int32)

    def loss_full(params):
      return tfm.causal_lm_loss(
          state.apply_fn({"params": params}, tokens), tokens)

    def loss_blocked(params):
      hidden = state.apply_fn({"params": params}, tokens,
                              return_hidden=True)
      return tfm.causal_lm_loss_blocked(
          hidden, tfm.tied_embedding_table(params), tokens, chunk=16)

    l1, g1 = jax.value_and_grad(loss_full)(state.params)
    l2, g2 = jax.value_and_grad(loss_blocked)(state.params)
    assert abs(float(l1) - float(l2)) < 1e-5, (float(l1), float(l2))
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g1, g2)))
    assert err < 1e-4, err

  def test_z_loss_matches_between_full_and_blocked(self):
    """The z-loss term (z·mean(logsumexp²), the PaLM/T5X logit
    stabilizer) raises the loss and agrees between the full and the
    blocked (fused-projection) implementations."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=97, num_layers=2, num_heads=2,
                                d_model=32, d_ff=64, max_seq_len=50,
                                dtype=jnp.float32)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=50)
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, 97, (3, 50)), jnp.int32)
    logits = state.apply_fn({"params": state.params}, tokens)
    hidden = state.apply_fn({"params": state.params}, tokens,
                            return_hidden=True)
    table = tfm.tied_embedding_table(state.params)

    base = float(tfm.causal_lm_loss(logits, tokens))
    zf = float(tfm.causal_lm_loss(logits, tokens, z_loss=1e-2))
    zb = float(tfm.causal_lm_loss_blocked(hidden, table, tokens,
                                          chunk=16, z_loss=1e-2))
    assert zf > base
    assert abs(zf - zb) < 1e-4, (zf, zb)

  def test_blocked_loss_trains(self):
    """A model trained with the blocked loss learns the same cyclic task
    the full-loss test uses (end-to-end through jax.checkpoint+scan)."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=2, num_heads=2,
                                d_model=64, d_ff=128, remat=False)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg,
                             learning_rate=3e-3, seq_len=24)
    cycle = np.tile(np.arange(8), 10)
    tokens = jnp.asarray(np.stack([cycle[i:i + 24] for i in range(8)]),
                         jnp.int32)

    @jax.jit
    def step(state, tokens):
      def loss_fn(p):
        hidden = state.apply_fn({"params": p}, tokens, return_hidden=True)
        return tfm.causal_lm_loss_blocked(
            hidden, tfm.tied_embedding_table(p), tokens, chunk=8)
      loss, grads = jax.value_and_grad(loss_fn)(state.params)
      return state.apply_gradients(grads=grads), loss

    for _ in range(150):
      state, loss = step(state, tokens)
    assert float(loss) < 0.1, float(loss)


class TestTransformerPipelineGQA:
  def test_pipeline_step_with_gqa(self):
    """The 1F1B full-model step composes with grouped-query attention
    (K/V of fewer heads, mesh-free inside the stage bodies): loss/grads
    stay finite and match the same config's dense sequential AD."""
    from tensorflowonspark_tpu.models import transformer as tfm
    from tensorflowonspark_tpu.parallel import mesh as M

    mesh = M.build_mesh(M.MeshSpec(data=2, pipeline=2),
                        devices=jax.devices()[:4])
    cfg = tfm.TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
        d_model=32, d_ff=64, max_seq_len=8, dtype=jnp.float32,
        remat=False)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=8)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (8, 8)), jnp.int32)
    lm_step = tfm.make_pipeline_train_step(cfg, mesh, num_microbatches=2)
    loss, grads = jax.jit(lm_step)(state.params, tokens)

    def dense_loss(p):
      return tfm.causal_lm_loss(
          tfm.Transformer(cfg, None).apply({"params": p}, tokens), tokens)

    ref_l, ref_g = jax.value_and_grad(dense_loss)(state.params)
    np.testing.assert_allclose(float(loss), float(ref_l), atol=1e-5,
                               rtol=1e-5)
    f0, _ = jax.flatten_util.ravel_pytree(grads)
    f1, _ = jax.flatten_util.ravel_pytree(ref_g)
    np.testing.assert_allclose(np.asarray(f0), np.asarray(f1),
                               atol=1e-4, rtol=1e-4)


class TestTransformerPipeline:
  """Full-model 1F1B pipeline training (make_pipeline_train_step): loss
  and EVERY grad — tied embed table (both stage contributions), blocks,
  final norm — must match single-device dense AD."""

  def _setup(self):
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=128, num_layers=4, num_heads=4,
                                d_model=64, d_ff=128, max_seq_len=16,
                                dtype=jnp.float32, remat=False)
    state = tfm.create_state(jax.random.PRNGKey(0), cfg, seq_len=16)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 128, (8, 16)), jnp.int32)

    def ref_loss(p):
      logits = tfm.Transformer(cfg, None).apply({"params": p}, tokens)
      return tfm.causal_lm_loss(logits, tokens)

    return tfm, cfg, state.params, tokens, ref_loss

  @pytest.mark.parametrize("n_stages,n_micro", [(4, 4), (2, 2), (4, 2)])
  def test_matches_dense_ad(self, n_stages, n_micro):
    from tensorflowonspark_tpu.parallel import mesh as M
    tfm, cfg, params, tokens, ref_loss = self._setup()
    l_ref, g_ref = jax.value_and_grad(ref_loss)(params)
    mesh = M.build_mesh(M.MeshSpec(pipeline=n_stages),
                        devices=jax.devices()[:n_stages])
    step = tfm.make_pipeline_train_step(cfg, mesh, num_microbatches=n_micro)
    loss, grads = jax.jit(step)(params, tokens)
    np.testing.assert_allclose(float(loss), float(l_ref),
                               atol=1e-5, rtol=1e-5)
    flat_p, _ = jax.flatten_util.ravel_pytree(grads)
    flat_r, _ = jax.flatten_util.ravel_pytree(g_ref)
    np.testing.assert_allclose(np.asarray(flat_p), np.asarray(flat_r),
                               atol=2e-4, rtol=2e-4)

  def test_dp_x_pp(self):
    from tensorflowonspark_tpu.parallel import mesh as M
    tfm, cfg, params, tokens, ref_loss = self._setup()
    l_ref, g_ref = jax.value_and_grad(ref_loss)(params)
    mesh = M.build_mesh(M.MeshSpec(data=2, pipeline=4),
                        devices=jax.devices())
    step = tfm.make_pipeline_train_step(cfg, mesh, num_microbatches=4)
    loss, grads = jax.jit(step)(params, tokens)
    np.testing.assert_allclose(float(loss), float(l_ref),
                               atol=1e-5, rtol=1e-5)
    flat_p, _ = jax.flatten_util.ravel_pytree(grads)
    flat_r, _ = jax.flatten_util.ravel_pytree(g_ref)
    np.testing.assert_allclose(np.asarray(flat_p), np.asarray(flat_r),
                               atol=2e-4, rtol=2e-4)

  def test_partition_roundtrip(self):
    tfm, cfg, params, _, _ = self._setup()
    outer, stage = tfm.pipeline_partition_params(params, 2)
    rebuilt = tfm.pipeline_unpartition_grads(outer, stage, 4)
    flat_a, _ = jax.flatten_util.ravel_pytree(params)
    flat_b, _ = jax.flatten_util.ravel_pytree(rebuilt)
    np.testing.assert_array_equal(np.asarray(flat_a), np.asarray(flat_b))

  def test_remat_stages_match(self):
    """cfg.remat=True must checkpoint stage blocks without changing math."""
    import dataclasses
    from tensorflowonspark_tpu.parallel import mesh as M
    tfm, cfg, params, tokens, ref_loss = self._setup()
    cfg_r = dataclasses.replace(cfg, remat=True)
    mesh = M.build_mesh(M.MeshSpec(pipeline=4), devices=jax.devices()[:4])
    step = tfm.make_pipeline_train_step(cfg_r, mesh, num_microbatches=4)
    loss, grads = jax.jit(step)(params, tokens)
    l_ref, g_ref = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss), float(l_ref),
                               atol=1e-5, rtol=1e-5)
    flat_p, _ = jax.flatten_util.ravel_pytree(grads)
    flat_r, _ = jax.flatten_util.ravel_pytree(g_ref)
    np.testing.assert_allclose(np.asarray(flat_p), np.asarray(flat_r),
                               atol=2e-4, rtol=2e-4)


class TestSlidingWindowModel:
  def test_windowed_flash_matches_dense_impl(self):
    """attention_window at the model level: the forced-flash production
    path and the dense path produce the same logits."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg_kw = dict(vocab_size=32, num_layers=2, num_heads=2, d_model=32,
                  d_ff=64, max_seq_len=128, remat=False,
                  dtype=jnp.float32, attention_window=24)
    flash_cfg = tfm.TransformerConfig(attention_impl="flash", **cfg_kw)
    dense_cfg = tfm.TransformerConfig(attention_impl="dense", **cfg_kw)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (2, 128)), jnp.int32)
    params = tfm.create_state(jax.random.PRNGKey(0), flash_cfg,
                              seq_len=128).params
    lf = tfm.Transformer(flash_cfg).apply({"params": params}, tokens)
    ld = tfm.Transformer(dense_cfg).apply({"params": params}, tokens)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ld), atol=1e-4,
                               rtol=1e-4)
    # and the window actually changes the result vs full attention
    full_cfg = tfm.TransformerConfig(attention_impl="dense",
                                     **dict(cfg_kw, attention_window=0))
    lfull = tfm.Transformer(full_cfg).apply({"params": params}, tokens)
    assert float(jnp.max(jnp.abs(ld - lfull))) > 1e-3

  def test_windowed_kv_decode_matches_recompute(self):
    """KV-cache decode with a sliding window must match full-recompute
    windowed decoding token for token."""
    from tensorflowonspark_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=16, num_layers=2, num_heads=2,
                                d_model=64, d_ff=128, max_seq_len=32,
                                remat=False, dtype=jnp.float32,
                                attention_window=6)
    state = tfm.create_state(jax.random.PRNGKey(3), cfg,
                             learning_rate=3e-3, seq_len=24)
    cycle = np.tile(np.arange(8), 10)
    tokens = jnp.asarray(np.stack([cycle[i:i + 24] for i in range(8)]),
                         jnp.int32)

    @jax.jit
    def step(state, tokens):
      def loss_fn(p):
        return tfm.causal_lm_loss(
            state.apply_fn({"params": p}, tokens), tokens)
      loss, grads = jax.value_and_grad(loss_fn)(state.params)
      return state.apply_gradients(grads=grads), loss

    for _ in range(150):
      state, _ = step(state, tokens)
    prompt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    full = tfm.greedy_generate(state.params, cfg, prompt, num_steps=10)
    kv = tfm.greedy_generate_kv(state.params, cfg, prompt, num_steps=10)
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(full))


class TestCachedAttention:
  """``transformer._cached_attention``: dense decode attention computed on
  the cache AS STORED ([b, max, kv_heads * head_dim]) and AS IT WAS before
  the query block's own keys/values are written, against a plain f32
  einsum over the 4-D view of the cache WITH the block written in, on a
  RANDOM cache (entries from the cursor on hold garbage the mask must
  hide)."""

  B, H, MAX = 3, 4, 64

  @staticmethod
  def _reference(q, k4, v4, q_pos, window):
    """[b, seg, h, d] attention in f32 over K/V [b, max, hk, d]."""
    b, seg, h, d = q.shape
    g = h // k4.shape[2]
    kf = jnp.repeat(k4.astype(jnp.float32), g, axis=2)
    vf = jnp.repeat(v4.astype(jnp.float32), g, axis=2)
    with jax.default_matmul_precision("highest"):
      s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kf) / d ** 0.5
      k_pos = jnp.arange(k4.shape[1])
      keep = k_pos <= q_pos[..., None]
      if window:
        keep = jnp.logical_and(keep, k_pos > q_pos[..., None] - window)
      s = jnp.where(keep[:, None], s, -jnp.inf)
      return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vf)

  @pytest.mark.parametrize("cache", ["bf16", "int8"])
  @pytest.mark.parametrize("window", [0, 5])
  @pytest.mark.parametrize("seg", [1, 3, 40])       # 40 x 4 heads: 4-D form
  @pytest.mark.parametrize("cursor", ["scalar", "per_slot"])
  @pytest.mark.parametrize("kv_heads", [4, 2])      # MHA, GQA
  @pytest.mark.parametrize("head_dim", [64, 128])
  def test_matches_f32_reference(self, head_dim, kv_heads, cursor, seg,
                                 window, cache):
    from tensorflowonspark_tpu.models import transformer as tfm
    b, h, mx, d = self.B, self.H, self.MAX, head_dim
    assert (seg * h <= tfm._MXU_COLS) == (seg < 40)  # both forms are run
    rng = np.random.RandomState(head_dim + 7 * kv_heads + seg + window)
    # the int8 case runs an f32 q: its output is f32, so the check is
    # tight enough to see a probability rounded to bf16 on its way to V
    q_dt = jnp.float32 if cache == "int8" else jnp.bfloat16
    q = jnp.asarray(rng.randn(b, seg, h, d), q_dt)
    if cache == "int8":
      k8 = rng.randint(-127, 128, (b, mx, kv_heads, d)).astype(np.int8)
      v8 = rng.randint(-127, 128, (b, mx, kv_heads, d)).astype(np.int8)
      ks = rng.uniform(0.002, 0.02, (b, mx, kv_heads)).astype(np.float32)
      vs = rng.uniform(0.002, 0.02, (b, mx, kv_heads)).astype(np.float32)
      k4, v4 = k8 * ks[..., None], v8 * vs[..., None]
      stored, scales = (k8, v8), dict(k_scale=jnp.asarray(ks),
                                      v_scale=jnp.asarray(vs))
    else:
      k4 = jnp.asarray(rng.randn(b, mx, kv_heads, d), jnp.bfloat16)
      v4 = jnp.asarray(rng.randn(b, mx, kv_heads, d), jnp.bfloat16)
      stored, scales = (k4, v4), {}
    start = np.asarray([3, 17, 11]) if cursor == "per_slot" \
        else np.asarray([9])
    q_pos = jnp.asarray(start[:, None] + np.arange(seg)[None])
    # the block's own keys/values, as the cache will hold them (an int8
    # cache: the dequantized numbers); the reference reads them FROM the
    # cache, written at the block's positions
    own_k = jnp.asarray(rng.randn(b, seg, kv_heads, d), jnp.bfloat16)
    own_v = jnp.asarray(rng.randn(b, seg, kv_heads, d), jnp.bfloat16)
    rows = np.arange(b)[:, None]
    at = np.broadcast_to(np.asarray(q_pos), (b, seg))
    k4 = jnp.asarray(k4, jnp.float32).at[rows, at].set(
        own_k.astype(jnp.float32))
    v4 = jnp.asarray(v4, jnp.float32).at[rows, at].set(
        own_v.astype(jnp.float32))
    out = tfm._cached_attention(
        q, own_k, own_v,
        *(jnp.asarray(x).reshape(b, mx, kv_heads * d) for x in stored),
        q_pos, window=window, **scales)
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = self._reference(q, k4, v4, q_pos, window)
    tol = 2e-5 if q_dt == jnp.float32 else 2 ** -7   # one bf16 rounding
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)

  def test_cache_contract_rounds_neither_operand(self):
    """An f32 operand against a bf16 cache: the stacked bf16 terms ARE the
    f32 number, so the product matches float64 to f32's accuracy (a
    single bf16 rounding of the operand would miss by about 2^-9)."""
    from tensorflowonspark_tpu.models import transformer as tfm
    rng = np.random.RandomState(0)
    p = jax.nn.softmax(jnp.asarray(rng.randn(2, 5, 96), jnp.float32), -1)
    v = jnp.asarray(rng.randn(2, 96, 256), jnp.bfloat16)
    terms = tfm._bf16_terms(p)
    assert len(terms) == 3 and all(t.dtype == jnp.bfloat16 for t in terms)
    np.testing.assert_array_equal(
        np.asarray(sum(t.astype(jnp.float32) for t in terms)), np.asarray(p))
    got = np.asarray(tfm._cache_contract("bnk,bkc->bnc", p, v), np.float64)
    want = np.einsum("bnk,bkc->bnc", np.asarray(p, np.float64),
                     np.asarray(v.astype(jnp.float32), np.float64))
    assert np.abs(got - want).max() < 1e-6
    rounded = np.einsum(
        "bnk,bkc->bnc",
        np.asarray(p.astype(jnp.bfloat16).astype(jnp.float32), np.float64),
        np.asarray(v.astype(jnp.float32), np.float64))
    assert np.abs(rounded - want).max() > 1e-4        # the test has teeth
