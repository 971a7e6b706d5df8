import os

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col
from daft_tpu.datatype import DataType
from daft_tpu.functions.ai import classify_image, classify_text, embed_image, embed_text, prompt


@pytest.fixture
def image_df():
    imgs = np.random.default_rng(0).integers(0, 255, (12, 32, 32, 3), dtype=np.uint8)
    return daft_tpu.from_pydict({
        "img": daft_tpu.Series.from_numpy(imgs, "img", DataType.image("RGB", 32, 32)),
        "txt": [f"sample text {i}" for i in range(12)],
    })


def test_embed_image(image_df):
    out = image_df.with_column(
        "emb", embed_image(col("img"), provider="flax_random", model="tiny")
    )
    assert out.schema["emb"].dtype == DataType.embedding(DataType.float32(), 32)
    embs = out.to_pydict()["emb"]
    assert len(embs) == 12
    v = np.asarray(embs[0])
    assert v.shape == (32,)
    assert abs(float(np.linalg.norm(v)) - 1.0) < 1e-3  # normalised


def test_embed_image_deterministic(image_df):
    e = embed_image(col("img"), provider="flax_random", model="tiny")
    a = image_df.with_column("emb", e).to_pydict()["emb"]
    b = image_df.with_column("emb", e).to_pydict()["emb"]
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]), rtol=1e-5)


def test_embed_text(image_df):
    out = image_df.with_column(
        "emb", embed_text(col("txt"), provider="flax_random", model="tiny")
    ).to_pydict()
    assert np.asarray(out["emb"][0]).shape == (64,)
    # Same text -> same embedding (hashing tokenizer + fixed seed)
    df2 = daft_tpu.from_pydict({"txt": ["sample text 0", "sample text 0"]})
    embs = df2.with_column(
        "emb", embed_text(col("txt"), provider="flax_random", model="tiny")
    ).to_pydict()["emb"]
    np.testing.assert_allclose(np.asarray(embs[0]), np.asarray(embs[1]), rtol=1e-5)


def test_classify(image_df):
    out = image_df.with_column(
        "lbl", classify_image(col("img"), ["cat", "dog"], provider="flax_random", model="tiny")
    ).to_pydict()
    assert set(out["lbl"]) <= {"cat", "dog"}
    out2 = image_df.with_column(
        "lbl", classify_text(col("txt"), ["a", "b"], provider="flax_random", model="tiny")
    ).to_pydict()
    assert set(out2["lbl"]) <= {"a", "b"}


def test_prompt(image_df):
    out = image_df.limit(2).with_column(
        "resp", prompt(col("txt"), provider="flax_random", model="tiny", max_new_tokens=4)
    ).to_pydict()
    assert len(out["resp"]) == 2
    assert all(isinstance(r, str) for r in out["resp"])


def test_provider_registry():
    from daft_tpu.ai.provider import load_provider

    p = load_provider("flax_random")
    desc = p.get_image_embedder("tiny")
    assert desc.get_provider() == "flax"
    assert desc.get_dimensions() == 32
    with pytest.raises(Exception):
        load_provider("nope")


def test_encoded_bytes_images():
    import io

    from PIL import Image as PILImage

    raws = []
    for i in range(4):
        buf = io.BytesIO()
        PILImage.new("RGB", (20, 20), (i * 20, 0, 0)).save(buf, format="PNG")
        raws.append(buf.getvalue())
    df = daft_tpu.from_pydict({"raw": daft_tpu.Series.from_pylist(raws, "raw", DataType.binary())})
    out = df.with_column("emb", embed_image(col("raw"), provider="flax_random", model="tiny")).to_pydict()
    assert np.asarray(out["emb"][0]).shape == (32,)


def test_all_providers_registered():
    from daft_tpu.ai.provider import load_provider

    for name in ("transformers", "openai", "google", "lm_studio", "vllm"):
        p = load_provider(name)
        assert p.name == name
    # API providers without credentials give actionable errors at
    # instantiation (worker), not at lookup (plan time).
    desc = load_provider("openai").get_text_embedder()
    with pytest.raises(Exception, match="OPENAI_API_KEY"):
        desc.instantiate()


def test_file_runtime(tmp_path):
    from daft_tpu.io.file import File, file_series

    p = tmp_path / "x.txt"
    p.write_bytes(b"hello")
    s = file_series([b"inline", str(p), None], "f")
    assert s.dtype == daft_tpu.DataType.file()
    files = s.to_pylist()
    assert files[0].read() == b"inline"
    assert files[1].read() == b"hello"
    assert files[1].size() == 5
    assert files[2] is None

    @daft_tpu.udf.func(return_dtype=daft_tpu.DataType.int64())
    def size_of(f):
        return None if f is None else len(f.read())

    df = daft_tpu.from_pydict({"f": s})
    out = df.select(size_of(col("f")).alias("n")).to_pydict()
    assert out["n"] == [6, 5, None]


def test_orbax_checkpoint_roundtrip(tmp_path):
    import jax

    from daft_tpu.models.checkpoint import load_params, save_params
    from daft_tpu.models.minilm import MiniLMConfig, init_minilm_params

    _, params = init_minilm_params(MiniLMConfig.tiny(), seed=7)
    d = str(tmp_path / "ckpt")
    save_params(params, d)
    _, fresh = init_minilm_params(MiniLMConfig.tiny(), seed=99)
    restored = load_params(d, fresh)
    a = jax.tree_util.tree_leaves(params)
    b = jax.tree_util.tree_leaves(restored)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_weights_path_orbax_dir(tmp_path):
    from daft_tpu.functions.ai import embed_text
    from daft_tpu.models.checkpoint import save_params
    from daft_tpu.models.minilm import MiniLMConfig, init_minilm_params

    _, params = init_minilm_params(MiniLMConfig.tiny(), seed=7)
    d = str(tmp_path / "w")
    save_params(params, d)
    df = daft_tpu.from_pydict({"t": ["hello"]})
    e1 = df.with_column("e", embed_text(col("t"), provider="flax", model="tiny",
                                        weights_path=d, seed=7)).to_pydict()["e"][0]
    e2 = df.with_column("e", embed_text(col("t"), provider="flax_random", model="tiny",
                                        seed=7)).to_pydict()["e"][0]
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=1e-5)


@pytest.mark.parametrize("rows, chunks", [(10, 3), (4, 1), (0, 0)])
def test_staged_ahead_and_staged_in_the_call_agree(rows, chunks):
    """The same images as a host array (the call pads and stages its own
    chunks) and as ``stage_images`` made of them (staged before the call)
    give the same embeddings; the forward's span counts what ran."""
    from daft_tpu.ai.flax_provider import FlaxCLIPImageEmbedder
    from daft_tpu.profiling import device_span, newest_device_span

    imgs = np.random.default_rng(1).integers(0, 255, (rows, 32, 32, 3), dtype=np.uint8)
    emb = FlaxCLIPImageEmbedder("tiny", batch_size=4)  # 10 rows: 4, 4 and a ragged 2
    outs = {}
    for form in ("host", "staged"):
        with device_span("test.mark") as mark:
            pass
        outs[form] = emb.embed_image(imgs if form == "host" else emb.stage_images(imgs))
        assert outs[form].shape == (rows, emb.dimensions)
        forward = newest_device_span("provider.forward")
        if rows == 0:  # nothing to run: no forward, no span
            assert forward is None or forward.span_id < mark.span_id
            continue
        assert forward.span_id > mark.span_id
        assert (forward.count["rows"], forward.count["chunks"]) == (rows, chunks)
        assert forward.count.get("staged") == (1 if form == "staged" else None)
        assert "mode" not in forward.count
    np.testing.assert_allclose(outs["host"], outs["staged"], rtol=1e-5)


def test_building_ai_expressions_initialises_no_backend():
    """A driver that only plans must not take the chip: importing the
    package, building embed_image / embed_text / prompt expressions and
    running the optimizer over them leave JAX without a backend."""
    import subprocess
    import sys

    code = """
import daft_tpu
from daft_tpu import col
from daft_tpu.functions.ai import embed_image, embed_text, prompt
df = daft_tpu.from_pydict({"img": [b"x"], "t": ["a"]})
q = (df.with_column("e", embed_image(col("img"), provider="flax_random", model="tiny"))
       .with_column("f", embed_text(col("t"), provider="flax_random", model="tiny"))
       .with_column("g", prompt(col("t"), provider="flax_random", model="tiny-lm")))
q.explain(show_all=True)
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "planning initialised a backend"
print("planned-without-backend")
"""
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "planned-without-backend" in proc.stdout


def test_image_embedder_defaults_are_constants():
    """The default device batch is a constant, the UDF's morsel batch is
    derived from the options alone, and how a batch is staged is no option."""
    from daft_tpu.ai import flax_provider as fp

    emb = fp.FlaxCLIPImageEmbedder("tiny")
    assert emb.max_batch == 128 and not [k for k in dir(emb) if "staging" in k]
    prov = fp.FlaxProvider(random_init=True)
    assert prov.get_image_embedder("tiny").get_udf_options().batch_size == 256
    desc = prov.get_image_embedder("tiny", batch_size=512)
    assert desc.get_udf_options().batch_size == 512
    inst = desc.instantiate()
    assert inst.max_batch == 512 and not [k for k in dir(inst) if "staging" in k]


# -- release: the instances go once the calls in flight have ended ------------------------------
class _HeldDescriptor:
    """A protocol whose call waits until it is let go, so that a test holds one in flight."""

    def __init__(self):
        from daft_tpu.ai.protocols import UDFOptions

        import threading

        self.options, self.made = UDFOptions(batch_size=4), 0
        self.entered, self.go = threading.Event(), threading.Event()

    def get_udf_options(self):
        return self.options

    def runs_beside_host(self):
        return False

    def instantiate(self):
        self.made += 1
        return object()


def _held_udf():
    from daft_tpu.functions.ai import _ProtocolUdf

    d = _HeldDescriptor()

    def call(inst, s):
        d.entered.set()
        assert d.go.wait(30)
        return s

    return d, _ProtocolUdf(d, call, DataType.int64(), "held")


def test_release_waits_for_the_call_in_flight_and_then_drops_the_instances():
    import threading

    from daft_tpu.errors import DaftExecutionError
    from daft_tpu.series import Series

    d, udf = _held_udf()
    s = Series.from_pylist([1, 2, 3], "x")
    worker = threading.Thread(target=udf.fn, args=(s,))
    worker.start()
    assert d.entered.wait(30)
    assert udf.release(wait_s=0.05) is False and len(udf._instances) == 1  # still running: nothing is dropped under it
    d.go.set()
    assert udf.release() is True and not udf._instances
    worker.join(30)
    with pytest.raises(DaftExecutionError, match="released"):  # no second set of parameters for a late call
        udf.fn(s)
    assert d.made == 1


def test_what_ships_to_another_process_holds_no_instance_and_no_call():
    from daft_tpu.functions.ai import _ProtocolUdf

    d, udf = _held_udf()
    d.go.set()
    udf.fn(daft_tpu.Series.from_pylist([1], "x"))
    assert udf.release() is True
    state = udf.__getstate__()
    assert state["_instances"] == {} and (state["_calls"], state["_released"]) == (0, False)
    assert "_idle" not in state and "_instance_lock" not in state
    again = _ProtocolUdf.__new__(_ProtocolUdf)
    again.__setstate__(state)
    assert again._get_instance() is not None and again.release() is True
