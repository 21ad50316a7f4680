"""Serving drivers for both system halves:

1. Ultrasound: one probe streaming RF acquisitions through the serving
   loop (serve_multitenant with one stream, 2 batches in flight) and
   report sustained FPS / MB/s plus the completion-latency distribution.
2. LM: slot-batched greedy decoding with prefill + KV cache (qwen3 smoke
   config) — the decode-cell path of the dry-run, runnable on CPU.

  PYTHONPATH=src python examples/serve_pipeline.py
"""

from repro.core import tiny_config
from repro.launch.scheduler import BatchPolicy, StreamSpec, serve_multitenant


def serve_ultrasound(n_frames: int = 32, max_batch: int = 4):
    cfg = tiny_config(nz=32, nx=32, n_f=8, n_c=16)
    probe = StreamSpec("probe0", cfg, fps=1e9, n_frames=n_frames,
                       deadline_ms=200.0)
    stats = serve_multitenant(
        [probe], policy=BatchPolicy(max_batch=max_batch), in_flight=2)
    lat = stats["latency"]
    print(f"ultrasound {stats['name']}: {stats['acquisitions']} acquisitions "
          f"({stats['frames']} frames) FPS={stats['fps']:.1f} "
          f"MB/s={stats['sustained_mbps']:.2f} "
          f"p50={lat['p50_s'] * 1e3:.2f}ms p95={lat['p95_s'] * 1e3:.2f}ms "
          f"miss_rate={stats['deadline_miss_rate']:.2f}")


def serve_lm():
    from repro.configs import get_smoke
    from repro.train.serving import serve_session
    cfg = get_smoke("qwen3-8b")
    out, stats = serve_session(cfg, requests=8, batch=4, prompt_len=32,
                               max_new=16)
    print(f"lm qwen3-8b(smoke): {stats['tokens']} tokens in "
          f"{stats['wall_s']:.2f}s = {stats['tok_per_s']:.0f} tok/s, "
          f"outputs {out.shape}")


if __name__ == "__main__":
    serve_ultrasound()
    serve_lm()
