"""Run a serving cell with the timed path broken underneath, for the tests.

    python faulty_serve.py <fault> <run.py arguments...>

``token``: every served token is replaced by the next id, where it is
sampled.  ``kv``: every prompt's cache has the KV of its last chunk
(``chunk_tokens`` positions before the prompt's last token) zeroed, where
it is built.  ``first_chunk``: before every plan the store drops the
document's first stored segment, where it holds more of the document, so
the plan computes the first chunk alone before a stored segment: the path
of the program's own first-chunk fault.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"     # before anything imports JAX

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parent.parent / "src"))


def break_tokens() -> None:
    from repro.serve.session import SessionManager

    orig = SessionManager._sample

    def sample(self, s):
        orig(self, s)
        tok = (s.out_tokens[-1] + 1) % self.model.cfg.vocab_size
        s.out_tokens[-1] = s.next_tok = tok
    SessionManager._sample = sample


def break_kv() -> None:
    import jax

    from repro.serve.engine import PrefixCacheBuilder

    orig = PrefixCacheBuilder.build_prefix

    def build(self, doc, length, **kw):
        out = orig(self, doc, length, **kw)
        lo = max(length - self.chunk, 0)

        def zero(path, x):
            if getattr(path[-1], "key", None) in ("k", "v"):
                return x.at[:, :, lo:length].set(0)
            return x
        caches = jax.tree_util.tree_map_with_path(zero, out[0])
        return (caches, *out[1:])
    PrefixCacheBuilder.build_prefix = build


def break_first_chunk() -> None:
    from repro.serve.session import SessionManager

    orig = SessionManager.submit

    def submit(self, sid, *args, **kw):
        doc_id = self.sessions[sid].doc_id
        segs = sorted((s for s in self.store._segs.values()
                       if doc_id in s.doc_ids()), key=lambda s: s.rng.lo)
        if len(segs) > 1 and segs[0].rng.lo == 0 \
                and segs[0].seg_id not in self.store._pins:
            self.store._evict(segs[0])
        return orig(self, sid, *args, **kw)
    SessionManager.submit = submit


if __name__ == "__main__":
    fault = sys.argv[1]
    {"token": break_tokens, "kv": break_kv,
     "first_chunk": break_first_chunk}[fault]()
    import run

    sys.exit(run.main(sys.argv[2:]))
