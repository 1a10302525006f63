"""Example scripts are runnable user surface — smoke them as subprocesses.

(The ResNet example is exercised on real TPU only: XLA:CPU compiles its
28x28 convolutions for minutes, which the LLM example doesn't suffer.)
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args):
    env = {k: v for k, v in os.environ.items()
           if k != "TPU_WORKER_HOSTNAMES"}
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + ["--xla_force_host_platform_device_count=8"]
    )
    return subprocess.run(
        [sys.executable, *args], env=env, capture_output=True, text=True,
        timeout=900, cwd=REPO,
    )


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["pp", "tp_sp"])
def test_llm_example_runs(mode):
    out = _run([
        "examples/train_llm_3d.py", "--mode", mode, "--max_epochs", "1",
    ])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "epoch 0: loss" in out.stdout


@pytest.mark.slow
def test_int8_serving_example_runs(tmp_path):
    out = _run([
        "examples/serve_llm_int8.py", "--preset", "toy", "--tp", "2",
        "--prompt_len", "8", "--new_tokens", "4", "--batch", "2",
        "--ckpt_dir", str(tmp_path / "ck"),
    ])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "serve:" in out.stdout and "load:" in out.stdout


@pytest.mark.slow
def test_int8_serving_long_context_flash(tmp_path):
    """The long-context serving composition: the
    same checkpoint served at a different window (--max_seq_len) with
    flash prefill (--flash) and the unrolled fallback (--unrolled) all
    drive to completion."""
    ck = str(tmp_path / "ck")
    out = _run([
        "examples/serve_llm_int8.py", "--preset", "toy",
        "--max_seq_len", "128", "--prompt_len", "48", "--new_tokens", "4",
        "--batch", "2", "--flash", "--ckpt_dir", ck,
    ])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "serve:" in out.stdout
    out2 = _run([
        "examples/serve_llm_int8.py", "--preset", "toy", "--unrolled",
        "--prompt_len", "8", "--new_tokens", "4", "--batch", "2",
        "--ckpt_dir", ck,  # reuses the checkpoint written above
    ])
    assert out2.returncode == 0, out2.stderr[-2000:]
    assert "serve:" in out2.stdout


@pytest.mark.slow
def test_int8_serving_server_paged(tmp_path):
    """--server --paged threads the page-pool geometry end to end: the
    request-stream arm completes on a paged engine and the receipt
    carries the pool config plus the hbm_high_water_bytes claim."""
    import json

    json_path = str(tmp_path / "serving.json")
    out = _run([
        "examples/serve_llm_int8.py", "--preset", "toy",
        "--prompt_len", "8", "--new_tokens", "4", "--batch", "2",
        "--server", "--requests", "6", "--slots", "2",
        "--paged", "--page-size", "8",
        "--ckpt_dir", str(tmp_path / "ck"), "--json", json_path,
    ])
    assert out.returncode == 0, out.stderr[-2000:]
    with open(json_path) as f:
        receipt = json.load(f)
    assert receipt["paged"] == 1 and receipt["page_size"] == 8
    # --pool-pages 0 sizes the pool to the whole-slot footprint:
    # 2 slots x 64-token window / 8-token pages
    assert receipt["pool_pages"] == 16
    assert receipt["hbm_high_water_bytes"] > 0
    assert receipt["pages_in_use"] == 0  # drained clean


@pytest.mark.slow
def test_int8_serving_from_hf_checkpoint(tmp_path):
    """--hf_checkpoint serves a published-format (HF safetensors) Llama
    directory through the same quantize-on-load pipeline — the
    from_pretrained(load_in_8bit=True) twin, offline end to end."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
    )
    torch.manual_seed(0)
    transformers.LlamaForCausalLM(cfg).save_pretrained(
        str(tmp_path), safe_serialization=True
    )
    out = _run([
        "examples/serve_llm_int8.py", "--hf_checkpoint", str(tmp_path),
        "--prompt_len", "8", "--new_tokens", "4", "--batch", "2",
    ])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "HF layout" in out.stdout and "serve:" in out.stdout
