"""nidtlint (neuroimagedisttraining_tpu.analysis) — rule unit tests on
positive/negative fixtures, pragma mechanics, CLI exit codes, and the
tier-1 gate: the shipped tree must lint clean forever."""

import json
import os
import subprocess
import sys
import textwrap

from neuroimagedisttraining_tpu.analysis import lint_paths, lint_source
from neuroimagedisttraining_tpu.analysis.cli import main as cli_main
from neuroimagedisttraining_tpu.analysis.core import parse_pragmas

PACKAGE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "neuroimagedisttraining_tpu")


def lint(src, path="pkg/mod.py", rules=None):
    return lint_source(textwrap.dedent(src), path=path, rules=rules)


def rules_of(findings):
    return [f.rule for f in findings]


# ---------------- trace-safety ----------------

def test_trace_flags_host_sync_in_jit_decorated():
    fs = lint("""
        import jax

        @jax.jit
        def f(x):
            return float(x) + x.item()
        """)
    assert rules_of(fs) == ["trace-host-sync", "trace-host-sync"]


def test_trace_flags_partial_jit_decorator():
    fs = lint("""
        import functools
        import jax
        import numpy as np

        @functools.partial(jax.jit, static_argnums=0)
        def f(n, x):
            return np.asarray(x)
        """)
    assert rules_of(fs) == ["trace-host-sync"]


def test_trace_resolves_local_def_passed_to_jit():
    fs = lint("""
        import jax
        import numpy as np

        def build():
            def step_fn(x):
                return np.asarray(x)
            return jax.jit(step_fn)
        """)
    assert rules_of(fs) == ["trace-host-sync"]


def test_trace_resolves_vmap_lambda_np_random():
    fs = lint("""
        import jax
        import numpy as np

        def f(xs):
            return jax.vmap(lambda i: i * np.random.rand())(xs)
        """)
    # the same call is both a trace hazard and a global-stream draw
    assert rules_of(fs) == ["determinism-global-random", "trace-np-random"]


def test_trace_resolves_self_method_and_partial_wrapper():
    fs = lint("""
        import functools
        import jax

        class Engine:
            def _step_body(self, x):
                return jax.device_get(x)

            def _consensus(self, x, plan=None):
                return x.item()

            def _step_jit(self):
                return jax.jit(self._step_body)

            def _consensus_jit(self, plan):
                return jax.jit(functools.partial(self._consensus, plan=plan),
                               donate_argnums=(0,))
        """)
    assert rules_of(fs) == ["trace-host-sync", "trace-host-sync"]


def test_trace_flags_nested_helper_inside_traced_fn():
    fs = lint("""
        import jax

        def build():
            def step_fn(xs):
                def per_client(x):
                    return x.tolist()
                return jax.vmap(per_client)(xs)
            return jax.jit(step_fn)
        """)
    # per_client is flagged once even though it is doubly traced
    # (lexically inside step_fn AND passed to vmap)
    assert rules_of(fs) == ["trace-host-sync"]


def test_trace_resolves_grad_and_lax_combinators():
    fs = lint("""
        import jax
        from jax import lax

        def step(params, xs):
            def loss_fn(p):
                return float(p)

            def body(carry, x):
                return carry.item(), x

            g = jax.value_and_grad(loss_fn)(params)
            out, _ = lax.scan(body, g, xs)
            return out
        """)
    assert rules_of(fs) == ["trace-host-sync", "trace-host-sync"]


def test_trace_resolves_cond_branches_only():
    fs = lint("""
        from jax import lax

        def pick(pred, x):
            def stay(v):
                return v

            def sync(v):
                return v.tolist()

            return lax.cond(pred, stay, sync, x)
        """)
    assert rules_of(fs) == ["trace-host-sync"]


def test_trace_resolves_modern_jax_shard_map_spelling():
    fs = lint("""
        import jax

        def build(mesh, specs, tree):
            def block_fn(blk):
                return blk.item()

            return jax.shard_map(block_fn, mesh=mesh, in_specs=(specs,),
                                 out_specs=specs)(tree)
        """)
    assert rules_of(fs) == ["trace-host-sync"]


def test_trace_ignores_host_code_and_jnp():
    fs = lint("""
        import jax
        import jax.numpy as jnp
        import numpy as np

        def step_jit():
            def step_fn(x):
                return jnp.asarray(x) + 1  # jnp is trace-safe
            return jax.jit(step_fn)

        def host_driver(fn, x):
            out = fn(x)                    # calling a jitted fn is fine
            return float(np.asarray(jax.device_get(out)).mean())
        """)
    assert fs == []


# ---------------- engine-contract ----------------

def test_engine_missing_attrs_and_round_method():
    fs = lint("""
        from neuroimagedisttraining_tpu.engines.base import FederatedEngine

        class BadEngine(FederatedEngine):
            pass
        """, path="pkg/engines/bad.py")
    assert sorted(rules_of(fs)) == ["engine-attrs", "engine-attrs",
                                    "engine-round"]


def test_engine_signature_mismatch_against_base():
    fs = lint("""
        from neuroimagedisttraining_tpu.engines.base import FederatedEngine

        class SigEngine(FederatedEngine):
            name = "sig"
            supports_streaming = False

            def train(self, extra):
                return {}

            def client_sampling(self, idx):  # base: (self, round_idx)
                return idx
        """, path="pkg/engines/sig.py")
    assert sorted(rules_of(fs)) == ["engine-signature", "engine-signature"]


def test_engine_inherited_streaming_flag_but_own_name_required():
    fs = lint("""
        from neuroimagedisttraining_tpu.engines.base import FederatedEngine

        class MidEngine(FederatedEngine):
            name = "mid"
            supports_streaming = True

            def train(self):
                return {}

        class LeafEngine(MidEngine):
            pass  # inherits train/supports_streaming, but name collides
        """, path="pkg/engines/leaf.py")
    assert rules_of(fs) == ["engine-attrs"]
    assert "name" in fs[0].message


def test_engine_compliant_subclass_is_clean():
    fs = lint("""
        from neuroimagedisttraining_tpu.engines.base import FederatedEngine

        class GoodEngine(FederatedEngine):
            name = "good"
            supports_streaming = False

            def train(self):
                return {}

            def eval_global(self, params, bstats, split="test"):
                return {}
        """, path="pkg/engines/good.py")
    assert fs == []


def test_non_engine_classes_ignored():
    fs = lint("""
        class Helper:
            pass

        class Codec(dict):
            pass
        """, path="pkg/engines/util.py")
    assert fs == []


# ---------------- lock-discipline ----------------

def test_lock_flags_unlocked_send_only_under_distributed():
    src = """
        def relay(conn, payload):
            conn.sendall(payload)
        """
    assert rules_of(lint(src, path="pkg/distributed/t.py")) == ["lock-send"]
    # faults/ writes raw frames too (FaultyCommManager's torn-frame
    # sends) — same interleaving hazard, same rule scope
    assert rules_of(lint(src, path="pkg/faults/t.py")) == ["lock-send"]
    assert lint(src, path="pkg/engines/t.py") == []


def test_lock_and_async_rules_cover_ingest_module():
    """ISSUE 12: the sharded ingest plane rides the SAME discipline
    families — an unlocked worker-pipe send and a blocking call inside
    an asyncfl coroutine both fire against asyncfl/ingest.py paths (the
    kill-one-worker plane multiplies the threads sharing each pipe)."""
    ingest = "neuroimagedisttraining_tpu/asyncfl/ingest.py"
    fs = lint("""
        class Worker:
            def reply(self, conn, verdict):
                conn.send(("v", verdict))
        """, path=ingest)
    # the unlocked send fires lock-send; since ISSUE 13 the per-upload
    # ("v", ...) spelling ALSO fires the batching rule — both real
    assert rules_of(fs) == ["lock-send", "obs-pipe-per-upload"]
    fs = lint("""
        import time

        async def watch_worker(pipe):
            time.sleep(0.5)
        """, path=ingest, rules=["async-blocking-call"])
    assert rules_of(fs) == ["async-blocking-call"]


def test_lock_flags_unlocked_shared_map_mutations():
    fs = lint("""
        class Broker:
            def register(self, topic, conn, payload):
                self._subs.setdefault(topic, []).append(conn)
                self._retained[topic] = payload
        """, path="pkg/distributed/broker2.py")
    assert rules_of(fs) == ["lock-shared-map", "lock-shared-map"]


def test_lock_satisfied_inside_with_lock():
    fs = lint("""
        class Broker:
            def register(self, topic, conn, payload):
                with self._lock:
                    self._subs.setdefault(topic, []).append(conn)
                    self._retained[topic] = payload
                with self._wlocks[conn]:
                    conn.sendall(payload)
        """, path="pkg/distributed/broker2.py")
    assert fs == []


def test_lock_with_header_mutation_is_flagged():
    """The `with` header runs BEFORE the lock is acquired — a shared-map
    mutation there must still be flagged."""
    fs = lint("""
        import threading

        class Broker:
            def serve(self, conn, payload):
                with self._wlocks.setdefault(conn, threading.Lock()):
                    conn.sendall(payload)
        """, path="pkg/distributed/t.py")
    assert rules_of(fs) == ["lock-shared-map"]


def test_lock_nested_def_does_not_inherit_lock():
    fs = lint("""
        def serve(self, conn):
            with self._lock:
                def later():
                    conn.sendall(b"x")  # runs after the with exits
                return later
        """, path="pkg/distributed/t.py")
    assert rules_of(fs) == ["lock-send"]


# ---------------- determinism ----------------

def test_determinism_flags_global_stream_and_unseeded_rng():
    fs = lint("""
        import numpy as np

        def sample(n):
            np.random.seed(0)
            idx = np.random.choice(n, 2)
            g = np.random.default_rng()
            r = np.random.RandomState()
            return idx, g, r
        """)
    assert rules_of(fs) == ["determinism-global-random",
                            "determinism-global-random",
                            "determinism-unseeded-rng",
                            "determinism-unseeded-rng"]


def test_determinism_allows_seeded_generators():
    fs = lint("""
        import numpy as np

        def sample(seed, n):
            rs = np.random.RandomState(seed)
            rng = np.random.default_rng(seed + 1)
            return rs.permutation(n), rng.integers(0, n)
        """)
    assert fs == []


# ---------------- pragmas ----------------

def test_pragma_suppresses_with_justification():
    fs = lint("""
        import numpy as np

        np.random.seed(0)  # nidt: allow[determinism-global-random] -- reference-parity shim (fedavg_api.py:92-100)
        """)
    assert fs == []


def test_bare_pragma_is_itself_a_finding():
    fs = lint("""
        import numpy as np

        np.random.seed(0)  # nidt: allow[determinism-global-random]
        """)
    assert rules_of(fs) == ["pragma"]
    assert "justification" in fs[0].message


def test_pragma_unknown_rule_id_is_flagged():
    fs = lint("""
        x = 1  # nidt: allow[no-such-rule] -- why not
        """)
    assert rules_of(fs) == ["pragma"]
    assert "no-such-rule" in fs[0].message


def test_pragma_on_multiline_statement_end_line():
    fs = lint("""
        import numpy as np

        idx = np.sort(np.random.choice(range(10), 2,  # nidt: allow[determinism-global-random] -- parity shim
                                       replace=False))
        """)
    assert fs == []


def test_pragma_on_multiline_statement_first_line():
    fs = lint("""
        import numpy as np

        idx = np.sort(  # nidt: allow[determinism-global-random] -- parity shim
            np.random.choice(range(10), 2, replace=False))
        """)
    assert fs == []


def test_pragma_inside_class_body_cannot_excuse_class_finding():
    """A pragma buried in a method must not suppress a class-header
    finding — only a pragma on the flagged `class` line itself counts."""
    src = """
        from neuroimagedisttraining_tpu.engines.base import FederatedEngine

        class BadEngine(FederatedEngine):{pragma}
            supports_streaming = False

            def train(self):
                x = 1  # nidt: allow[engine-attrs] -- buried, must not count
                return x
        """
    buried = lint(src.format(pragma=""), path="pkg/engines/bad.py")
    assert rules_of(buried) == ["engine-attrs"]
    on_header = lint(src.format(
        pragma="  # nidt: allow[engine-attrs] -- fixture engine"),
        path="pkg/engines/bad.py")
    assert on_header == []


def test_parse_error_is_a_finding():
    fs = lint("def broken(:\n")
    assert rules_of(fs) == ["parse-error"]


# ---------------- shm-discipline (ISSUE 18) ----------------

def test_shm_owner_must_close_and_unlink():
    """A creator class missing EITHER teardown call is flagged at the
    creation site — one finding per missing call."""
    src = """
        from multiprocessing import shared_memory

        class LeakyWriter:
            def __init__(self, size):
                self.shm = shared_memory.SharedMemory(create=True,
                                                      size=size)

            def destroy(self):
                self.shm.close()  # close but never unlink: name leaks
        """
    assert rules_of(lint(src)) == ["shm-owner-teardown"]
    src_neither = """
        from multiprocessing import shared_memory

        class VeryLeakyWriter:
            def __init__(self, size):
                self.shm = shared_memory.SharedMemory(create=True,
                                                      size=size)
        """
    assert rules_of(lint(src_neither)) == ["shm-owner-teardown"] * 2


def test_shm_attacher_must_never_unlink():
    src = """
        from multiprocessing import shared_memory

        class GreedyReader:
            def __init__(self, name):
                self.shm = shared_memory.SharedMemory(name=name)

            def close(self):
                self.shm.close()
                self.shm.unlink()  # destroying a name it does not own
        """
    assert rules_of(lint(src)) == ["shm-attach-unlink"]


def test_shm_discipline_clean_lifecycles_and_aliases():
    """The correct asymmetric lifecycle is clean on both sides, and the
    rule resolves the import alias + positional create=True spelling."""
    src = """
        import multiprocessing.shared_memory as sm

        class Writer:
            def __init__(self, size):
                self.shm = sm.SharedMemory(None, True, size)

            def destroy(self):
                self.shm.close()
                self.shm.unlink()

        class Reader:
            def __init__(self, name):
                self.shm = sm.SharedMemory(name=name)

            def close(self):
                self.shm.close()
        """
    assert lint(src) == []


# ---------------- CLI + tier-1 gate ----------------

def test_cli_exits_nonzero_on_seeded_violations(tmp_path, capsys):
    bad = tmp_path / "distributed" / "t.py"
    bad.parent.mkdir()
    bad.write_text("def f(conn):\n    conn.sendall(b'x')\n")
    rc = cli_main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "lock-send" in out and str(bad) in out


def test_cli_json_mode(tmp_path, capsys):
    bad = tmp_path / "t.py"
    bad.write_text("import numpy as np\nnp.random.seed(1)\n")
    rc = cli_main(["--json", str(bad)])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert report and set(report[0]) == {"path", "line", "rule", "message"}
    assert report[0]["rule"] == "determinism-global-random"
    assert report[0]["line"] == 2


def test_cli_rule_selection_and_usage_errors(tmp_path, capsys):
    bad = tmp_path / "t.py"
    bad.write_text("import numpy as np\nnp.random.seed(1)\n")
    assert cli_main(["--rules", "lock-send", str(bad)]) == 0
    assert cli_main(["--rules", "bogus", str(bad)]) == 2
    assert cli_main([]) == 2
    capsys.readouterr()


def test_rule_selection_is_id_granular(tmp_path):
    """Selecting one id of a multi-id family must not surface the family's
    other ids: seed(1) is global-random, clean for unseeded-rng."""
    from neuroimagedisttraining_tpu.analysis import lint_source

    src = "import numpy as np\nnp.random.seed(1)\n"
    assert lint_source(src, rules=["determinism-unseeded-rng"]) == []
    assert [f.rule for f in lint_source(
        src, rules=["determinism-global-random"])] == [
        "determinism-global-random"]


def test_shipped_tree_is_clean():
    """THE tier-1 gate: every invariant holds (or carries a justified
    pragma) across the whole package, forever."""
    findings = lint_paths([PACKAGE_DIR])
    assert findings == [], "\n" + "\n".join(f.render() for f in findings)


def test_shipped_tree_clean_via_cli_subprocess():
    """Acceptance criterion verbatim: the module CLI exits 0 on the tree."""
    proc = subprocess.run(
        [sys.executable, "-m", "neuroimagedisttraining_tpu.analysis",
         PACKAGE_DIR],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_every_shipped_pragma_carries_a_justification():
    """Acceptance criterion: every `# nidt: allow[...]` in the tree has a
    one-line reason (also enforced at lint time by the pragma rule)."""
    from neuroimagedisttraining_tpu.analysis.core import iter_py_files

    seen = 0
    for fp in iter_py_files([PACKAGE_DIR]):
        with open(fp, encoding="utf-8") as fh:
            for pragma in parse_pragmas(fh.read()).values():
                seen += 1
                assert pragma.justification, (fp, pragma.line)
                assert pragma.rule_ids, (fp, pragma.line)
    assert seen >= 10  # the reference-parity shims are annotated


# ---------------- donation discipline (ISSUE 4) ----------------

def test_donation_missing_flags_undeclared_round_jit():
    fs = lint("""
        import jax

        class E:
            @property
            def _round_jit(self):
                def round_fn(params, bstats):
                    return params
                return jax.jit(round_fn)
        """, rules=["donation-missing"])
    assert rules_of(fs) == ["donation-missing"]


def test_donation_missing_accepts_gating_call_and_pragma():
    fs = lint("""
        import jax

        class E:
            @property
            def _round_jit(self):
                def round_fn(params, bstats):
                    return params
                return jax.jit(round_fn,
                               donate_argnums=self._donate_argnums(0, 1))

            @property
            def _consensus_jit(self):
                def consensus_fn(per):
                    return per
                return jax.jit(consensus_fn)  # nidt: allow[donation-missing] -- outputs alias no input shape
        """, rules=["donation-missing"])
    assert fs == []


def test_donation_missing_ignores_non_round_jits():
    fs = lint("""
        import jax

        def make():
            def eval_all(params, X):
                return params
            return jax.jit(eval_all)
        """, rules=["donation-missing"])
    assert fs == []


def test_donation_use_after_donate_flags_read():
    fs = lint("""
        import jax

        class E:
            @property
            def _round_jit(self):
                def round_fn(params, bstats):
                    return params, bstats
                return jax.jit(round_fn, donate_argnums=(0, 1))

            def train(self, params, bstats):
                out, new_b = self._round_jit(params, bstats)
                leak = params
                return out, leak
        """, rules=["donation-use-after-donate"])
    assert rules_of(fs) == ["donation-use-after-donate"]


def test_donation_use_after_donate_same_statement_rebind_is_clean():
    fs = lint("""
        import jax

        class E:
            @property
            def _round_jit(self):
                def round_fn(params, bstats, rngs):
                    return params, bstats, 0.0
                return jax.jit(round_fn,
                               donate_argnums=self._donate_argnums(0, 1))

            def train(self, params, bstats, rngs):
                for r in range(3):
                    params, bstats, loss = self._round_jit(params, bstats,
                                                           rngs)
                return params, bstats, rngs  # rngs was never donated
        """, rules=["donation-use-after-donate"])
    assert fs == []


def test_donation_use_after_donate_resolves_jit_factories():
    fs = lint("""
        import jax

        class E:
            def _round_jit_for(self, plan):
                def round_fn(per, b, M):
                    return per, b
                return jax.jit(round_fn, donate_argnums=(0, 1))

            def train(self, per, b, plan, M):
                out = self._round_jit_for(plan)(per, b, M)
                stale = per
                return out, stale
        """, rules=["donation-use-after-donate"])
    assert rules_of(fs) == ["donation-use-after-donate"]
    # ...and the factory's own argument (plan) is NOT treated as donated
    assert "'per'" in fs[0].message


def test_donation_use_after_donate_rebind_then_read_is_clean():
    fs = lint("""
        import jax

        class E:
            @property
            def _round_jit(self):
                def round_fn(params):
                    return params
                return jax.jit(round_fn, donate_argnums=(0,))

            def train(self, params):
                out = self._round_jit(params)
                params = out
                return params
        """, rules=["donation-use-after-donate"])
    assert fs == []


# ---------------- Byzantine layer coverage (ISSUE 5) ----------------

def test_byzantine_layer_modules_lint_clean_standalone():
    """faults/adversary.py and core/robust.py are inside the lexical net
    and clean on their own (not just as part of the whole-tree gate):
    the jitted attack transforms and order-statistic aggregators carry
    no host syncs, no global RNG, no unseeded streams."""
    for rel in ("faults/adversary.py", "core/robust.py"):
        fs = lint_paths([os.path.join(PACKAGE_DIR, rel)])
        assert fs == [], rel + "\n" + "\n".join(f.render() for f in fs)


def test_trace_safety_catches_adversary_shaped_violation():
    """The exact idiom faults/adversary.py uses — a per-client transform
    CALLED from a vmapped lambda — is covered by the transitive-call
    closure: host numpy RNG inside it is a trace finding (the attack
    must draw from jax.random so one seed replays in both
    federations). Before ISSUE 5 the resolver stopped at the call
    boundary and this idiom escaped the net entirely."""
    fs = lint("""
        import jax
        import numpy as np

        def apply_attack(u, ref, mult):
            noise = np.random.normal(size=u.shape)
            return ref + (u - ref) * mult + noise

        def apply_attack_stacked(us, ref, mults):
            return jax.vmap(
                lambda u, m: apply_attack(u, ref, m))(us, mults)
        """)
    # the same draw is both a global-stream read and a trace hazard
    assert rules_of(fs) == ["determinism-global-random", "trace-np-random"]


def test_trace_safety_catches_host_sync_in_weiszfeld_body():
    """An eager .item() escape inside a lax.fori_loop body (the
    geometric_median Weiszfeld shape) is a trace-safety finding."""
    fs = lint("""
        import jax

        def geometric_median(stacked, iters):
            def step(_, z):
                return z * float(jax.numpy.sum(z).item())
            return jax.lax.fori_loop(0, iters, step, stacked)
        """)
    assert "trace-host-sync" in rules_of(fs)


def test_determinism_rule_covers_schedule_shaped_rng():
    """The byz_prob transient stream must ride the seeded FaultSchedule
    draw: an unseeded default_rng in a schedule-shaped module is a
    determinism finding."""
    fs = lint("""
        import numpy as np

        def byzantine_kind(round_idx, rank, p):
            return np.random.default_rng().random() < p
        """, rules=["determinism-unseeded-rng"])
    assert rules_of(fs) == ["determinism-unseeded-rng"]


# ---------------- mesh discipline (ISSUE 6) ----------------

def test_shardmap_missing_specs_flagged():
    fs = lint("""
        from jax.experimental.shard_map import shard_map

        def f(block, mesh, x):
            return shard_map(block, mesh=mesh)(x)
        """, rules=["mesh-shardmap-specs"])
    assert rules_of(fs) == ["mesh-shardmap-specs"]
    assert "in_specs and out_specs" in fs[0].message


def test_shardmap_partial_specs_flagged_and_full_specs_pass():
    fs = lint("""
        from jax import shard_map

        def f(block, mesh, x, spec):
            return shard_map(block, mesh=mesh, in_specs=(spec,))(x)
        """, rules=["mesh-shardmap-specs"])
    assert rules_of(fs) == ["mesh-shardmap-specs"]
    assert "out_specs" in fs[0].message
    assert lint("""
        from jax.experimental.shard_map import shard_map

        def f(block, mesh, x, spec):
            return shard_map(block, mesh=mesh, in_specs=(spec,),
                             out_specs=spec)(x)
        """, rules=["mesh-shardmap-specs"]) == []


def test_pad_weights_adhoc_mask_flagged():
    fs = lint("""
        import jax.numpy as jnp

        def weights(ns, n_real):
            return jnp.where(jnp.arange(ns.shape[0]) < n_real, ns, 0)
        """, path="neuroimagedisttraining_tpu/engines/base.py",
        rules=["mesh-pad-weights"])
    assert rules_of(fs) == ["mesh-pad-weights"]
    assert "pad_row_weights" in fs[0].message


def test_pad_weights_helper_home_and_other_compares_pass():
    # the helper's own home is exempt
    assert lint("""
        import jax.numpy as jnp

        def pad_row_weights(ns, n_real):
            return jnp.where(jnp.arange(ns.shape[0]) < n_real, ns, 0)
        """, path="neuroimagedisttraining_tpu/parallel/cohort.py",
        rules=["mesh-pad-weights"]) == []
    # sample-validity masks (arange vs a per-client count) are not the
    # pad-row idiom and stay legal
    assert lint("""
        import jax.numpy as jnp

        def valid(X, nc):
            return jnp.arange(X.shape[0]) < nc
        """, rules=["mesh-pad-weights"]) == []


# ---------------- async discipline (ISSUE 7) ----------------

_ASYNCFL_PATH = "neuroimagedisttraining_tpu/asyncfl/loadgen.py"


def test_async_blocking_calls_flagged_in_asyncfl():
    fs = lint("""
        import time
        import select

        async def drive(sock):
            time.sleep(0.1)
            select.select([sock], [], [])
            sock.recv(4)
            sock.accept()
        """, path=_ASYNCFL_PATH, rules=["async-blocking-call"])
    assert rules_of(fs) == ["async-blocking-call"] * 4
    assert "freezes every coroutine" in fs[0].message


def test_async_awaited_and_nested_sync_bodies_pass():
    # awaited calls are the sanctioned non-blocking spellings; nested
    # SYNC defs/lambdas are executor-shipped bodies and may block
    assert lint("""
        import asyncio
        import time

        async def drive(loop, sock):
            await asyncio.sleep(0.1)
            data = await loop.sock_recv(sock, 4)

            def off_loop():
                time.sleep(1)
                return sock.recv(4)
            return await loop.run_in_executor(None, off_loop)
        """, path=_ASYNCFL_PATH, rules=["async-blocking-call"]) == []


def test_async_rules_scoped_to_asyncfl_and_sync_defs_exempt():
    src = """
        import time

        def sync_helper():
            time.sleep(1)

        async def drive(sock):
            time.sleep(1)
        """
    # outside asyncfl/ the family never fires
    assert lint(src, path="neuroimagedisttraining_tpu/distributed/x.py",
                rules=["async-blocking-call"]) == []
    # inside, only the async body is flagged — module-level sync code
    # (the selector loop itself) blocks legitimately
    fs = lint(src, path=_ASYNCFL_PATH, rules=["async-blocking-call"])
    assert len(fs) == 1 and fs[0].line == 8


def test_async_nested_coroutine_violation_reported_once():
    fs = lint("""
        import time

        async def outer():
            async def inner():
                time.sleep(1)
            return inner
        """, path=_ASYNCFL_PATH, rules=["async-blocking-call"])
    assert rules_of(fs) == ["async-blocking-call"]
    assert "inner" in fs[0].message


def test_async_queue_get_flagged_dict_get_passes():
    fs = lint("""
        async def drain(q, d):
            item = q.get()
            known = d.get("key")
            timed = q.get(timeout=0.1)
            nonblock = q.get(block=False)
        """, path=_ASYNCFL_PATH, rules=["async-queue-get"])
    assert rules_of(fs) == ["async-queue-get"]
    assert fs[0].line == 3


# ---------------- obs-discipline (ISSUE 9) ----------------

def test_obs_clock_in_jitted_body_flagged():
    fs = lint("""
        import time
        import jax

        @jax.jit
        def f(x):
            t0 = time.perf_counter()
            return x + time.monotonic() - t0
        """, rules=["obs-clock-in-trace"])
    assert rules_of(fs) == ["obs-clock-in-trace", "obs-clock-in-trace"]
    assert "trace-time clock value" in fs[0].message


def test_obs_clock_aliased_import_and_vmap_lambda():
    fs = lint("""
        from time import perf_counter
        import jax

        def g(xs):
            return jax.vmap(lambda x: x * perf_counter())(xs)
        """, rules=["obs-clock-in-trace"])
    assert rules_of(fs) == ["obs-clock-in-trace"]


def test_obs_clock_at_host_boundary_passes():
    fs = lint("""
        import time
        import jax

        @jax.jit
        def f(x):
            return x * 2

        def driver(x):
            t0 = time.perf_counter()
            y = f(x)
            return y, time.perf_counter() - t0
        """, rules=["obs-clock-in-trace"])
    assert fs == []


def test_obs_metrics_mutation_in_trace_flagged():
    fs = lint("""
        import jax
        from neuroimagedisttraining_tpu.obs import metrics as obs_metrics

        COUNTER = obs_metrics.counter("x_total")

        @jax.jit
        def f(x):
            COUNTER.inc()
            obs_metrics.gauge("g").set(1)
            return x
        """, rules=["obs-metrics-in-trace"])
    # .inc() via the method heuristic, the gauge() call via the obs
    # package prefix
    assert rules_of(fs) == ["obs-metrics-in-trace", "obs-metrics-in-trace"]


def test_obs_metrics_transitive_callee_flagged():
    """The trace-safety resolver's transitive closure: a helper CALLED
    from a traced body is traced too, so its histogram observe is
    caught."""
    fs = lint("""
        import jax

        def note(h, v):
            h.observe(v)

        def f(h, xs):
            return jax.vmap(lambda x: note(h, x) or x)(xs)
        """, rules=["obs-metrics-in-trace"])
    assert rules_of(fs) == ["obs-metrics-in-trace"]


def test_obs_indexed_set_and_host_mutation_pass():
    fs = lint("""
        import jax
        from neuroimagedisttraining_tpu.obs import metrics as obs_metrics

        @jax.jit
        def f(x, i):
            return x.at[i].set(0.0)  # jnp indexed update, not a gauge

        def host_boundary(c):
            c.inc()
            obs_metrics.gauge("g").set(2)
        """, rules=["obs-metrics-in-trace"])
    assert fs == []


# -- obs-sync-in-trace (ISSUE 14: the dispatch profiler's zero-sync rule)


def test_obs_sync_in_jitted_body_flagged():
    """block_until_ready inside a traced body — both the jax dotted
    call and the zero-arg array method — is the hidden-sync class the
    dispatch profiler's wiring must never introduce."""
    fs = lint("""
        import jax

        @jax.jit
        def f(x):
            jax.block_until_ready(x)
            return x.block_until_ready() + 1
        """, rules=["obs-sync-in-trace"])
    assert rules_of(fs) == ["obs-sync-in-trace", "obs-sync-in-trace"]
    assert "zero-sync" in fs[0].message


def test_obs_sync_transitive_callee_flagged():
    fs = lint("""
        import jax

        def wait(x):
            return jax.block_until_ready(x)

        def f(xs):
            return jax.vmap(lambda x: wait(x) + 1)(xs)
        """, rules=["obs-sync-in-trace"])
    assert rules_of(fs) == ["obs-sync-in-trace"]


def test_obs_sync_at_host_boundary_passes():
    """The blessed pattern: time around the ENQUEUE on the host, sync
    only at host boundaries (what obs/compute.note_dispatch and the
    bench cells do)."""
    fs = lint("""
        import time
        import jax

        @jax.jit
        def f(x):
            return x * 2

        def driver(x):
            t0 = time.perf_counter()
            y = f(x)
            jax.block_until_ready(y)
            return y, time.perf_counter() - t0
        """, rules=["obs-sync-in-trace"])
    assert fs == []


# ---------------- obs fan-in discipline (ISSUE 13) ----------------

_INGEST_PATH = "neuroimagedisttraining_tpu/asyncfl/ingest.py"
_MESSAGE_PATH = "neuroimagedisttraining_tpu/distributed/message.py"


def test_trace_ctx_literal_in_add_get_flagged():
    fs = lint("""
        def stamp(msg, ctx):
            msg.add("trace_ctx", ctx)

        def read(msg):
            return msg.get("trace_ctx")
        """, rules=["obs-trace-ctx-key"])
    assert rules_of(fs) == ["obs-trace-ctx-key", "obs-trace-ctx-key"]
    assert "ARG_TRACE_CTX" in fs[0].message


def test_trace_ctx_constant_spelling_and_definition_site_pass():
    # spelled through the constant: clean
    fs = lint("""
        from neuroimagedisttraining_tpu.distributed import message as M

        def stamp(msg, ctx):
            msg.add(M.ARG_TRACE_CTX, ctx)
            other = msg.get("round_idx")
        """, rules=["obs-trace-ctx-key"])
    assert fs == []
    # the definition site itself may spell the literal
    fs = lint("""
        ARG_TRACE_CTX = "trace_ctx"

        def demo(msg):
            return msg.get("trace_ctx")
        """, path=_MESSAGE_PATH, rules=["obs-trace-ctx-key"])
    assert fs == []


def test_unbatched_pipe_send_in_ingest_flagged():
    fs = lint("""
        class W:
            def receive_message(self, msg):
                self.conn.send(("beat", self.wid, msg.sender_id))

            def per_upload(self, verdict):
                self.conn.send(("v", self.wid, verdict))
        """, path=_INGEST_PATH, rules=["obs-pipe-per-upload"])
    assert rules_of(fs) == ["obs-pipe-per-upload",
                            "obs-pipe-per-upload"]
    assert "batch" in fs[0].message


def test_batched_pipe_sends_and_other_modules_pass():
    src = """
        class W:
            def _flush_locked(self):
                self.conn.send(("beats", self.wid, sorted(self.pend)))
                self.conn.send(("vb", self.wid, self.counts, self.taus))
                self.conn.send(("obs", self.wid, payload))
                self.conn.send(("reg", self.wid, c))
        """
    assert lint(src, path=_INGEST_PATH,
                rules=["obs-pipe-per-upload"]) == []
    # the rule is scoped to asyncfl/ingest.py — a ("beat", ...) tuple
    # elsewhere (e.g. a test fixture) is not its business
    assert lint("""
        def elsewhere(conn):
            conn.send(("beat", 0, 1))
        """, rules=["obs-pipe-per-upload"]) == []


# ---------------- precision-discipline (ISSUE 10) ----------------

def test_precision_upcast_astype_in_traced_body_flagged():
    fs = lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            return jnp.sum(x.astype(jnp.float32))
        """, path="pkg/core/mod.py", rules=["precision-upcast"])
    assert rules_of(fs) == ["precision-upcast"]
    assert "re-widens" in fs[0].message


def test_precision_upcast_asarray_and_constructor_flagged():
    fs = lint("""
        import jax
        import jax.numpy as jnp

        def f(xs):
            return jax.vmap(lambda x: jnp.asarray(x, jnp.float32)
                            + jnp.float32(2.0))(xs)
        """, path="pkg/ops/mod.py", rules=["precision-upcast"])
    assert sorted(rules_of(fs)) == ["precision-upcast", "precision-upcast"]


def test_precision_upcast_transitive_callee_flagged():
    """The rule rides the trace-safety resolver: an upcast in a helper
    CALLED from a traced body is caught like a decorated one."""
    fs = lint("""
        import jax
        import jax.numpy as jnp

        def widen(x):
            return x.astype(jnp.float32)

        @jax.jit
        def step(x):
            return widen(x) * 2
        """, path="pkg/models/mod.py", rules=["precision-upcast"])
    assert rules_of(fs) == ["precision-upcast"]


def test_precision_upcast_out_of_scope_and_host_pass():
    """engines/ aggregation tails (f32 master weights by contract) and
    host-side code are out of the rule's reach; model-dtype casts pass."""
    src = """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def round_tail(w):
            return w.astype(jnp.float32)

        def host(x):
            return x.astype(jnp.float32)
        """
    assert lint(src, path="pkg/engines/mod.py",
                rules=["precision-upcast"]) == []
    fs = lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, dtype):
            return x.astype(dtype) + jnp.zeros((4,), jnp.float32)
        """, path="pkg/core/mod.py", rules=["precision-upcast"])
    assert fs == []  # threading a dtype / f32 zeros-construction are fine


def test_precision_upcast_pragma_suppresses_with_reason():
    fs = lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            return x.astype(jnp.float32)  # nidt: allow[precision-upcast] -- blessed loss site
        """, path="pkg/core/mod.py", rules=["precision-upcast"])
    assert fs == []


# ---------------- round-program discipline (ISSUE 11) ----------------

def test_round_program_flags_hand_rolled_fused_scan():
    """A lax.scan inside a *round*/*fused*-named method of an engine
    class is a hand-rolled fused round body — the builder
    (engines/program.py) owns the K-round scan."""
    fs = lint("""
        import jax
        from neuroimagedisttraining_tpu.engines.base import FederatedEngine

        class E(FederatedEngine):
            name = "e"
            supports_streaming = False

            def train(self):
                pass

            def _fused_round_jit(self, k):
                def fused_round_fn(params, xs):
                    return jax.lax.scan(lambda c, x: (c, c), params, xs)
                return jax.jit(fused_round_fn,
                               donate_argnums=self._donate_argnums(0))
        """, path="pkg/engines/mod.py",
        rules=["round-program-fused-body"])
    assert rules_of(fs) == ["round-program-fused-body"]


def test_round_program_allows_scan_outside_engines_and_in_builder():
    src = """
        import jax

        def fused_round_fn(params, xs):
            return jax.lax.scan(lambda c, x: (c, c), params, xs)
        """
    # module-level scan (no engine class): fine
    assert lint(src, path="pkg/engines/mod.py",
                rules=["round-program-fused-body"]) == []
    # the builder itself: exempt by file
    engine_src = """
        import jax
        from neuroimagedisttraining_tpu.engines.base import FederatedEngine

        class E(FederatedEngine):
            name = "e"
            supports_streaming = False

            def train(self):
                pass

            def _fused_round_jit(self, k):
                def fused_round_fn(params, xs):
                    return jax.lax.scan(lambda c, x: (c, c), params, xs)
                return jax.jit(fused_round_fn,
                               donate_argnums=self._donate_argnums(0))
        """
    assert lint(engine_src, path="pkg/engines/program.py",
                rules=["round-program-fused-body"]) == []


def test_round_program_allows_non_round_scan_in_engine():
    """Scans in non-round methods (phase-1 scoring, eval chunking) stay
    legal — only the fused-round naming convention is fenced."""
    fs = lint("""
        import jax
        from neuroimagedisttraining_tpu.engines.base import FederatedEngine

        class E(FederatedEngine):
            name = "e"
            supports_streaming = False

            def train(self):
                pass

            def _scores_body(self, xs):
                return jax.lax.scan(lambda c, x: (c, c), 0, xs)
        """, path="pkg/engines/mod.py",
        rules=["round-program-fused-body"])
    assert fs == []


def test_round_program_reason_must_be_table_key():
    base = """
        from neuroimagedisttraining_tpu.engines.base import FederatedEngine

        class E(FederatedEngine):
            name = "e"
            supports_streaming = False

            def train(self):
                pass

            def cohort_fallback_key(self):
                return {key}
        """
    fs = lint(base.format(key="'my ad-hoc reason string'"),
              path="pkg/engines/mod.py", rules=["round-program-reason"])
    assert rules_of(fs) == ["round-program-reason"]
    assert lint(base.format(key="'mpc-host-boundary'"),
                path="pkg/engines/mod.py",
                rules=["round-program-reason"]) == []
    assert lint(base.format(key="None"),
                path="pkg/engines/mod.py",
                rules=["round-program-reason"]) == []


def test_round_program_reason_keys_parse_from_source():
    from neuroimagedisttraining_tpu.analysis.round_program import (
        _reason_keys,
    )

    keys = _reason_keys()
    assert "no-sharded-body" in keys
    assert "mpc-host-boundary" in keys
    assert not any("fused" in k for k in keys)
    assert "gossip-mesh-collectives" in keys
