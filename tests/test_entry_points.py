"""The main path through the entry points' own main()s, at a tiny size:
seeded corpus -> finetune.main --save -> tools/run_text_generation_server.py
--load (a real process) -> one PUT /api -> SIGTERM -> exit 0.

`chip_smoke.py` drives the same chain at Llama-2-7B widths on the chip;
this is its CPU shadow, so a flag that drifts between the trainer, the
checkpoint and the server tool fails tier-1 instead of a chip run.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
from http.client import HTTPConnection

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_train_save_load_serve(tmp_path, capsys, monkeypatch):
    from megatron_llm_tpu.data.indexed_dataset import (
        MMapIndexedDatasetBuilder,
    )
    from megatron_llm_tpu.training.checkpointing import read_tracker

    # the entry points place the persistent compile cache themselves;
    # keep this run's out of the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    prefix = str(tmp_path / "corpus_text_document")
    rs = np.random.RandomState(0)
    builder = MMapIndexedDatasetBuilder(prefix + ".bin", dtype=np.int32)
    for _ in range(32):
        builder.add_item(
            rs.randint(1, 255, size=rs.randint(20, 200)).astype(np.int32))
        builder.end_document()
    builder.finalize(prefix + ".idx")

    import finetune

    ckpt = str(tmp_path / "ckpt")
    finetune.main([
        "--model_name", "llama2", "--model_size", "7", "--num_layers", "2",
        "--hidden_size", "64", "--num_attention_heads", "4",
        "--num_attention_heads_kv", "2", "--ffn_hidden_size", "128",
        "--seq_length", "64", "--max_position_embeddings", "64",
        "--micro_batch_size", "1", "--data_parallel_size", "1",
        "--train_iters", "3", "--bf16", "--recompute_granularity", "full",
        "--tokenizer_type", "NullTokenizer", "--null_vocab_size", "255",
        "--data_path", prefix, "--split", "100,0,0", "--save", ckpt,
        "--log_interval", "1", "--eval_interval", "1000",
        "--eval_iters", "0"])
    out = capsys.readouterr().out
    assert out.count("lm loss:") == 3 and "nan" not in out
    assert read_tracker(ckpt) == (3, False)

    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    server = subprocess.Popen(
        [sys.executable,
         os.path.join(_REPO, "tools", "run_text_generation_server.py"),
         "--load", ckpt, "--tokenizer_type", "NullTokenizer",
         "--null_vocab_size", "255", "--host", "127.0.0.1",
         "--port", str(port), "--serving_slots", "2", "--max_context", "64",
         "--page_size", "16", "--prefill_chunk_tokens", "16"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.time() + 180
        while True:
            assert server.poll() is None, server.stdout.read()
            assert time.time() < deadline, "server did not come up"
            try:
                conn = HTTPConnection("127.0.0.1", port, timeout=5)
                conn.request("GET", "/health")
                if conn.getresponse().status == 200:
                    break
            except OSError:
                time.sleep(0.5)
        conn = HTTPConnection("127.0.0.1", port, timeout=180)
        conn.request("PUT", "/api", json.dumps({
            "prompts": ["5 6 7 8 9"], "tokens_to_generate": 6, "top_k": 1}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200, body
        toks = [int(t) for t in body["text"][0].split()]
        assert toks[:5] == [5, 6, 7, 8, 9] and len(toks) == 11
        conn.request("GET", "/metrics")
        m = json.loads(conn.getresponse().read())
        assert m["serve_admitted"] == m["serve_retired"] == 1
        server.send_signal(signal.SIGTERM)  # graceful: drain, exit 0
        assert server.wait(timeout=60) == 0, server.stdout.read()
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()
