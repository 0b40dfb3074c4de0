"""tier-1 hook for tools/trace_lint.py — instrumentation coverage of
the obs plane can't silently rot (ISSUE 1 satellite): every public
coordinator/log/device-plane/interdc entry point must carry a span or
profiler annotation, checked statically."""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "tools"))
import trace_lint  # noqa: E402


def test_all_entry_points_instrumented():
    problems = trace_lint.lint(trace_lint.repo_root())
    assert not problems, "\n".join(problems)


def test_lint_detects_a_dark_entry_point(tmp_path):
    """The lint actually fires: a copy of the coordinator with the
    @traced decorators and tracer calls stripped must be flagged."""
    root = trace_lint.repo_root()
    for rel in trace_lint.ENTRY_POINTS:
        src = open(os.path.join(root, rel)).read()
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(src
                       .replace("@traced", "@_not_traced")
                       .replace("tracer.span", "tracer_span")
                       .replace("tracer.instant", "tracer_instant")
                       .replace("tracer.wait_span", "tracer_wait_span")
                       .replace("tracer.root", "tracer_root")
                       .replace("tracer.close_stamp", "tracer_close")
                       .replace("tracing.annotate", "tracing_annotate")
                       .replace("prof.annotate", "prof_annotate"))
    problems = trace_lint.lint(str(tmp_path))
    # every single entry point goes dark in the stripped copy (the
    # stripped interdc files additionally trip the ISSUE-6 publish
    # rule — counted separately below)
    entry = [p for p in problems if "no span/annotation" in p
             or "entry point missing" in p]
    n_points = sum(len(ms) for classes in trace_lint.ENTRY_POINTS.values()
                   for ms in classes.values())
    assert len(entry) == n_points
    assert any("transport.publish" in p for p in problems), \
        "stripped sender's publish sites should trip the publish rule"


def test_standalone_main_exit_code():
    assert trace_lint.main([]) == 0


def test_kernel_span_rule_flags_bare_jit(tmp_path):
    """ISSUE 2 rule: a public @jax.jit function under antidote_tpu/mat/
    without @kernel_span is flagged; private and decorated ones pass."""
    d = tmp_path / "antidote_tpu" / "mat"
    d.mkdir(parents=True)
    (d / "newstore.py").write_text(
        "import jax\n"
        "from jax import jit\n"
        "from functools import partial\n"
        "from antidote_tpu.obs.prof import kernel_span\n"
        "@jax.jit\n"
        "def bare_read(st):\n    return st\n"
        "@partial(jax.jit, donate_argnums=(0,))\n"
        "def bare_append(st):\n    return st\n"
        "@jit\n"
        "def bare_from_import(st):\n    return st\n"
        "@partial(jit, donate_argnums=(0,))\n"
        "def bare_from_import_partial(st):\n    return st\n"
        "@jit(donate_argnums=(0,))\n"
        "def bare_called_jit(st):\n    return st\n"
        "@kernel_span('mat.store')\n"
        "@jax.jit\n"
        "def good_read(st):\n    return st\n"
        "@jax.jit\n"
        "def _private_impl(st):\n    return st\n")
    problems = trace_lint.lint_kernel_spans(str(tmp_path))
    flagged = {p.split("::")[1].split(":")[0] for p in problems}
    assert flagged == {"bare_read", "bare_append", "bare_from_import",
                       "bare_from_import_partial", "bare_called_jit"}


def test_kernel_span_rule_clean_on_repo():
    assert trace_lint.lint_kernel_spans(trace_lint.repo_root()) == []


def test_kernel_span_rule_flags_jit_assignments(tmp_path):
    """ISSUE 4 rule: the ingest module's flush kernels are natural to
    land as module-level ``name = jax.jit(impl)`` assignments, which
    the decorator-only rule never saw — a public unwrapped jitted
    assignment under mat/ must be flagged; kernel_span-wrapped and
    private ones pass."""
    d = tmp_path / "antidote_tpu" / "mat"
    d.mkdir(parents=True)
    (d / "newingest.py").write_text(
        "import jax\n"
        "from functools import partial\n"
        "from antidote_tpu.obs.prof import kernel_span, profiler\n"
        "def _impl(st):\n    return st\n"
        "bare_flush = jax.jit(_impl)\n"
        "bare_partial_flush = partial(jax.jit, donate_argnums=(0,))(_impl)\n"
        "good_flush = kernel_span('mat.ingest')(jax.jit(_impl))\n"
        "good_wrapped = profiler.wrap(jax.jit(_impl), name='x')\n"
        "_private_flush = jax.jit(_impl)\n"
        "not_a_kernel = 7\n")
    problems = trace_lint.lint_kernel_spans(str(tmp_path))
    flagged = {p.split("::")[1].split(":")[0] for p in problems}
    assert flagged == {"bare_flush", "bare_partial_flush"}


def test_kernel_span_rule_covers_ingest_module():
    """The new ingest plane lives under mat/ (already a swept dir) and
    its public flush kernel really is kernel_span-wrapped — the
    profiler sees every packed flush."""
    from antidote_tpu.mat import ingest

    assert hasattr(ingest.packed_append, "__kernel_span__")
    assert ingest.packed_append.__kernel_span__[1] == "mat.ingest"


def test_kernel_span_rule_covers_interdc(tmp_path):
    """ISSUE 3 rule: the dependency-gate ring kernels live under
    antidote_tpu/interdc/, which the lint must sweep exactly like
    mat/ — a bare public @jax.jit there is a dark device kernel."""
    assert any(d.endswith(os.path.join("antidote_tpu", "interdc"))
               for d in trace_lint._KERNEL_SPAN_DIRS)
    d = tmp_path / "antidote_tpu" / "interdc"
    d.mkdir(parents=True)
    (d / "newgate.py").write_text(
        "import jax\n"
        "from functools import partial\n"
        "from antidote_tpu.obs.prof import kernel_span\n"
        "@partial(jax.jit, donate_argnums=(0,))\n"
        "def bare_ring_op(st):\n    return st\n"
        "@kernel_span('interdc.dep')\n"
        "@jax.jit\n"
        "def good_ring_op(st):\n    return st\n")
    problems = trace_lint.lint_kernel_spans(str(tmp_path))
    flagged = {p.split("::")[1].split(":")[0] for p in problems}
    assert flagged == {"bare_ring_op"}


def test_publish_rule_flags_untraced_publish_sites(tmp_path):
    """ISSUE 6 rule: a function under antidote_tpu/interdc/ calling
    transport.publish / bus.publish without a span or instant is a
    dark wire send; instrumented ones pass."""
    d = tmp_path / "antidote_tpu" / "interdc"
    d.mkdir(parents=True)
    (d / "newsender.py").write_text(
        "from antidote_tpu.obs.spans import tracer\n"
        "class S:\n"
        "    def dark_send(self, data):\n"
        "        self.transport.publish('dc', data)\n"
        "    def dark_bus_send(self, bus, data):\n"
        "        bus.publish('dc', data)\n"
        "    def good_send(self, data):\n"
        "        with tracer.span('interdc_send', 'interdc'):\n"
        "            self.transport.publish('dc', data)\n"
        "    def good_instant_send(self, data):\n"
        "        tracer.instant('interdc_send', 'interdc')\n"
        "        self.transport.publish('dc', data)\n"
        "    def unrelated(self, q):\n"
        "        q.publish_stats()\n")
    problems = trace_lint.lint_publish_spans(str(tmp_path))
    flagged = {p.split("::")[1].split(":")[0] for p in problems}
    assert flagged == {"dark_send", "dark_bus_send"}


def test_publish_rule_clean_on_repo():
    assert trace_lint.lint_publish_spans(trace_lint.repo_root()) == []


def test_decode_rule_flags_untraced_decode_sites(tmp_path):
    """ISSUE 7 rule: a function under interdc/ or cluster/ decoding a
    wire frame (frame_from_bin / *.from_bin) without a span/instant is
    a blind arrival site; instrumented ones and the decoder
    definitions themselves pass."""
    for sub in ("interdc", "cluster"):
        d = tmp_path / "antidote_tpu" / sub
        d.mkdir(parents=True)
        (d / "newrx.py").write_text(
            "from antidote_tpu.obs.spans import tracer\n"
            "from antidote_tpu.interdc.wire import frame_from_bin\n"
            "class R:\n"
            "    def dark_deliver(self, data):\n"
            "        return frame_from_bin(data)\n"
            "    def dark_relay(self, bins):\n"
            "        return [InterDcTxn.from_bin(b) for b in bins]\n"
            "    def good_deliver(self, data):\n"
            "        frame = frame_from_bin(data)\n"
            "        tracer.instant('interdc_rx', 'interdc')\n"
            "        return frame\n"
            "    def unrelated(self, data):\n"
            "        return data.decode()\n"
            "def frame_from_bin(data):\n"
            "    return data\n")
    problems = trace_lint.lint_decode_instants(str(tmp_path))
    flagged = sorted(p.split("::")[1].split(":")[0] for p in problems)
    assert flagged == ["dark_deliver", "dark_deliver",
                      "dark_relay", "dark_relay"]


def test_decode_rule_clean_on_repo():
    assert trace_lint.lint_decode_instants(trace_lint.repo_root()) == []


def test_fused_rule_flags_untraced_fused_read_sites(tmp_path):
    """ISSUE 8 rule: a function under mat/ dispatching a gathered
    fused_read fold without a span/instant is a dark serve-stage
    kernel; instrumented callers and the definition itself pass."""
    d = tmp_path / "antidote_tpu" / "mat"
    d.mkdir(parents=True)
    (d / "newserve.py").write_text(
        "from antidote_tpu.obs.spans import tracer\n"
        "from antidote_tpu.mat.device_plane import fused_read\n"
        "class S:\n"
        "    def dark_drain(self, splits):\n"
        "        return fused_read(splits)\n"
        "    def dark_attr(self, dp, splits):\n"
        "        return dp.fused_read(splits)\n"
        "    def good_drain(self, splits):\n"
        "        with tracer.span('read_serve_fold', 'device'):\n"
        "            return fused_read(splits)\n"
        "    def unrelated(self, x):\n"
        "        return x\n"
        "def fused_read(splits):\n"
        "    return splits\n")
    problems = trace_lint.lint_fused_spans(str(tmp_path))
    flagged = sorted(p.split("::")[1].split(":")[0] for p in problems)
    assert flagged == ["dark_attr", "dark_drain"]


def test_fused_rule_clean_on_repo():
    assert trace_lint.lint_fused_spans(trace_lint.repo_root()) == []


def test_sync_rule_flags_untraced_sync_sites(tmp_path):
    """ISSUE 9 rule: a function under oplog/ calling the durability
    barrier (sync/fsync/oplog_sync) without a span/instant is a dark
    commit-path disk stall; instrumented callers and the barrier
    definitions themselves (functions named ``sync``) pass."""
    d = tmp_path / "antidote_tpu" / "oplog"
    d.mkdir(parents=True)
    (d / "newlog.py").write_text(
        "import os\n"
        "from antidote_tpu.obs.spans import tracer\n"
        "class L:\n"
        "    def dark_commit(self):\n"
        "        self.log.sync()\n"
        "    def dark_raw(self, fd):\n"
        "        os.fsync(fd)\n"
        "    def dark_native(self, lib, h):\n"
        "        lib.oplog_sync(h)\n"
        "    def good_drain(self):\n"
        "        with tracer.span('log_group_drain', 'oplog'):\n"
        "            self.log.sync()\n"
        "    def good_inline(self):\n"
        "        tracer.instant('log_sync_inline', 'oplog')\n"
        "        self.log.sync()\n"
        "    def sync(self):\n"
        "        os.fsync(self.fd)\n"  # the barrier itself: exempt
        "    def unrelated(self):\n"
        "        return 1\n")
    problems = trace_lint.lint_sync_spans(str(tmp_path))
    flagged = sorted(p.split("::")[1].split(":")[0] for p in problems)
    assert flagged == ["dark_commit", "dark_native", "dark_raw"]


def test_sync_rule_clean_on_repo():
    assert trace_lint.lint_sync_spans(trace_lint.repo_root()) == []


def test_ckpt_rule_flags_untraced_ckpt_io_sites(tmp_path):
    """ISSUE 10 rule: a function under oplog/ performing checkpoint IO
    (write_doc / load_doc / truncate_below) without a span/instant is
    a dark cold-path disk move; instrumented callers and the IO
    definitions themselves pass."""
    d = tmp_path / "antidote_tpu" / "oplog"
    d.mkdir(parents=True)
    (d / "newckpt.py").write_text(
        "from antidote_tpu.obs.spans import tracer\n"
        "class P:\n"
        "    def dark_commit_ckpt(self, doc):\n"
        "        self.ckpt.write_doc(doc)\n"
        "    def dark_recover(self):\n"
        "        return self.ckpt.load_doc()\n"
        "    def dark_trunc(self, off):\n"
        "        self.log.truncate_below(off)\n"
        "    def good_commit(self, doc):\n"
        "        with tracer.span('ckpt_write', 'oplog'):\n"
        "            self.ckpt.write_doc(doc)\n"
        "    def good_trunc(self, off):\n"
        "        tracer.instant('ckpt_truncate', 'oplog')\n"
        "        self.log.truncate_below(off)\n"
        "    def write_doc(self, doc):\n"  # the IO itself: exempt
        "        return doc\n"
        "    def load_doc(self):\n"  # likewise\n
        "        return None\n"
        "    def unrelated(self):\n"
        "        return 1\n")
    problems = trace_lint.lint_ckpt_spans(str(tmp_path))
    flagged = sorted(p.split("::")[1].split(":")[0] for p in problems)
    assert flagged == ["dark_commit_ckpt", "dark_recover", "dark_trunc"]


def test_ckpt_rule_clean_on_repo():
    assert trace_lint.lint_ckpt_spans(trace_lint.repo_root()) == []
