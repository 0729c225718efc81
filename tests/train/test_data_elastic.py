"""Data-pipeline determinism (fault-tolerant replay) + elastic
checkpoint restore onto a different device topology."""
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import Prefetcher, SyntheticLM

SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


class TestSyntheticLM:
    def test_replay_determinism(self):
        """Resuming at step k regenerates byte-identical batches — the
        property that makes checkpoint-restart exact."""
        d1 = SyntheticLM(vocab_size=1000, seq_len=16, global_batch=4,
                         seed=3)
        d2 = SyntheticLM(vocab_size=1000, seq_len=16, global_batch=4,
                         seed=3)
        run1 = [d1.batch_at(i) for i in range(5)]
        run2 = [d2.batch_at(i) for i in (3, 4)]
        np.testing.assert_array_equal(run1[3]["tokens"],
                                      run2[0]["tokens"])
        np.testing.assert_array_equal(run1[4]["labels"],
                                      run2[1]["labels"])

    def test_labels_are_next_tokens(self):
        d = SyntheticLM(vocab_size=1000, seq_len=16, global_batch=2,
                        seed=0)
        b = d.batch_at(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:],
                                      b["labels"][:, :-1])

    def test_task_is_learnable_structure(self):
        """The Markov task has real next-token signal (low conditional
        entropy vs uniform)."""
        d = SyntheticLM(vocab_size=1000, seq_len=256, global_batch=8,
                        seed=0)
        b = d.batch_at(0)
        toks = np.asarray(b["tokens"]).reshape(-1)
        # structured: active vocabulary is a strict subset and the
        # unigram entropy sits clearly below uniform (the conditional
        # structure itself is proven by the trainer's loss decrease)
        vals, counts = np.unique(toks, return_counts=True)
        p = counts / counts.sum()
        ent = -(p * np.log(p)).sum()
        assert len(vals) < 600
        assert ent < 0.9 * np.log(1000)

    def test_prefetcher_preserves_order_and_count(self):
        d = SyntheticLM(vocab_size=100, seq_len=8, global_batch=2, seed=1)
        raw = [d.batch_at(i) for i in range(6)]
        pf = Prefetcher(iter(raw))
        got = list(pf)
        assert len(got) == 6
        np.testing.assert_array_equal(np.asarray(got[4]["tokens"]),
                                      raw[4]["tokens"])


@pytest.mark.slow
def test_elastic_restore_onto_sharded_mesh():
    """A checkpoint written on 1 device restores onto an 8-device mesh
    with per-leaf shardings (the elastic-restart path)."""
    from repro.train import checkpoint as C
    tree = {"w": jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16),
            "b": jnp.ones((16,), jnp.bfloat16)}
    with tempfile.TemporaryDirectory() as d:
        C.save(d, 7, tree)
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=8")
            import sys
            sys.path.insert(0, %r)
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.train import checkpoint as C
            from repro.sharding.specs import make_mesh
            mesh = make_mesh((8,), ("data",))
            template = {"w": jnp.zeros((64, 16), jnp.float32),
                        "b": jnp.zeros((16,), jnp.bfloat16)}
            sh = {"w": NamedSharding(mesh, P("data", None)),
                  "b": NamedSharding(mesh, P())}
            tree, step, _ = C.restore(%r, template, shardings=sh)
            assert step == 7
            assert len(tree["w"].sharding.device_set) == 8
            want = np.arange(64 * 16, dtype=np.float32).reshape(64, 16)
            np.testing.assert_array_equal(np.asarray(tree["w"]), want)
            print("OKELASTIC")
        """ % (SRC, d))
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OKELASTIC" in out.stdout
