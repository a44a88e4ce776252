"""Record `tests/recorded/spans.xplane.pb` on the chip (run once, by hand:
`chiprun -- python benchmark/tests/record_spans_trace.py <out_dir>`).

Inside the harness's window annotation: one program span (`busy`, opened
with the program's own `utils/telemetry.span`, so its profiler annotation
is what is recorded) around four calls of a jitted `fn` whose work sits
under the scopes of the fused search (`symbiont.qsearch` > `scan`, `topk`),
then one span (`idle`) around a sleep with nothing on the device. Writes
the trace and what `_host_spans.py` and `_scopes.py` make of it (the
test's expected numbers). The Python tracer is off: the file stays small.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "layer_metrics"), str(HERE.parent),
                str(HERE.parent.parent)]


def expected(path) -> dict:
    import _host_spans
    import _scopes

    got = _host_spans.read(path)
    w0, w1 = got["window"]
    return {
        "window_s": (w1 - w0) / 1e12,
        "span_s": {name: [(b - a) / 1e12 for a, b in spans]
                   for name, spans in sorted(got["spans"].items())},
        "idle_inside": {name: _host_spans.idle_inside_share(got, name)
                        for name in sorted(got["spans"])},
        "scopes": sorted(["/".join(k), op, v]
                         for (k, op), v in _scopes.by_path(path).items()),
    }


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    import trace_reduce
    from symbiont_tpu.utils.telemetry import span

    def fn(corpus, q):
        with jax.named_scope("symbiont.qsearch"):
            with jax.named_scope("scan"):
                scores = (corpus @ q).astype(jnp.float32)
            with jax.named_scope("topk"):
                return jax.lax.top_k(scores, 8)

    f = jax.jit(fn)
    corpus = jnp.ones((65536, 256), jnp.bfloat16)
    q = jnp.ones((256,), jnp.bfloat16)
    jax.block_until_ready(f(corpus, q))
    out = Path(out_dir)
    tmp = out / "raw"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    with jax.profiler.TraceAnnotation("benchmark.window"):
        with span("busy", {"X-Trace-Id": "trace-of-busy"}):
            for _ in range(4):
                jax.block_until_ready(f(corpus, q))
        time.sleep(0.002)
        with span("idle", {"X-Trace-Id": "trace-of-idle"}):
            time.sleep(0.005)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(tmp)
    shutil.copyfile(src, out / "spans.xplane.pb")
    shutil.rmtree(tmp)
    want = expected(out / "spans.xplane.pb")
    (out / "spans.expected.json").write_text(json.dumps(want, indent=1))
    print(json.dumps(want))


if __name__ == "__main__":
    main(sys.argv[1])
