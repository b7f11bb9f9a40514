"""Batched serving demo: prefill + decode rounds with streaming analysis
and window-adaptive policies.

    PYTHONPATH=src python examples/serve.py [--arch mixtral-8x7b] \
        [--tokens 8] [--rounds 3] [--schema paper|tpu] [--policies all]

Runs a reduced config of the chosen architecture, prefills a batch of
prompts, then decodes ``--tokens`` tokens per request per round.  Each round
is one collection window: the recorder is frozen and reset, the window is
handed to an AsyncAnalysisSession (analysis happens off the serving loop;
``--sync-analysis`` opts back into inline analysis), and the final report
shows the per-window timeline (regions: prefill / decode / detokenize).

``--policies`` attaches a ``core.policy.PolicyEngine`` to the window
stream; the PolicyLog tail is printed after every decode round, so the
detect -> decide loop is visible live (on this single-shard demo the
straggler policies stay quiet — the audit trail is the point).
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.core import (AnalysisSession, AsyncAnalysisSession, PolicyEngine,
                        RegionTree, make_policies)
from repro.models import init_params
from repro.models.model import decode_step, prefill
from repro.perfdbg import Instrumenter, RegionRecorder


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=8,
                    help="tokens decoded per request per round")
    ap.add_argument("--rounds", type=int, default=3,
                    help="decode rounds == analysis windows")
    ap.add_argument("--schema", default="paper", choices=("paper", "tpu"))
    ap.add_argument("--analysis-workers", type=int,
                    default=int(os.environ.get("PERFDBG_ANALYSIS_WORKERS",
                                               "1")),
                    help="analysis worker pool size (reports and policy "
                         "decisions are identical for any value; env "
                         "default PERFDBG_ANALYSIS_WORKERS)")
    ap.add_argument("--analysis-executor", default="thread",
                    choices=("thread", "process"),
                    help="thread (shared session) or process (spawn-pool "
                         "session replicas, past the GIL); reports are "
                         "identical either way")
    ap.add_argument("--sync-analysis", action="store_true",
                    help="analyze each round inline instead of on the "
                         "async worker thread")
    ap.add_argument("--policies", default="",
                    help="comma list of window-adaptive policies "
                         "(rebalance,reshard,quarantine or 'all')")
    ap.add_argument("--policy-window-k", type=int, default=2,
                    help="debounce: consecutive confirming windows before "
                         "a policy fires")
    args = ap.parse_args()
    if args.rounds < 1 or args.tokens < 1:
        ap.error("--rounds and --tokens must be >= 1")

    cfg = reduced_config(args.arch)
    params = init_params(cfg, 0)
    key = jax.random.PRNGKey(0)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    s_buf = args.prompt_len + args.rounds * args.tokens

    tree = RegionTree("serve")
    for nm in ("prefill", "decode", "detokenize"):
        tree.add(nm)
    rec = RegionRecorder(tree, 1, schema=args.schema)
    ins = Instrumenter(rec, 0)

    engine = None
    if args.policies:
        engine = PolicyEngine(make_policies(args.policies),
                              k=args.policy_window_k)

    def on_window(entry):
        cccrs = [tree.name(r) for r in entry.report.internal.cccrs]
        print(f"[{entry.title()}] internal bottlenecks: {cccrs or ['(none)']}")
        if engine is not None:   # the decide half of the closed loop, live
            print(f"[{entry.title()}] policy log tail:")
            for line in engine.log.render(3).splitlines():
                print(f"  {line}")

    if args.sync_analysis:
        session, pipe = AnalysisSession(tree), None
    else:
        # decode rounds only pay the snapshot copy; the analysis worker
        # drains the (bounded) queue behind the serving loop
        session, pipe = None, AsyncAnalysisSession(tree, max_queue=4,
                                                   workers=args.analysis_workers,
                                                   executor=args.analysis_executor,
                                                   on_window=on_window,
                                                   policy_engine=engine)
    io_kw = "host_io_bytes" if args.schema == "tpu" else "disk_io"

    prefill_j = jax.jit(lambda p, t: prefill(p, cfg, t, s_buf))
    decode_j = jax.jit(lambda p, t, pos, c: decode_step(p, cfg, t, pos, c))

    out_tokens = []
    cache = None
    decode_wall = 0.0
    sync_actions = []
    for rnd in range(args.rounds):
        with ins.program():
            if rnd == 0:
                with ins.region("prefill", instructions=2 * cfg.active_params()
                                * prompts.size):
                    logits, cache = prefill_j(params, prompts)
                    jax.block_until_ready(logits)
                out_tokens.append(
                    jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32))
            w0 = time.perf_counter()
            with ins.region("decode", instructions=2 * cfg.active_params()
                            * args.batch * args.tokens):
                for i in range(args.tokens):
                    pos = jnp.asarray(
                        args.prompt_len + rnd * args.tokens + i, jnp.int32)
                    logits, cache = decode_j(params, out_tokens[-1], pos, cache)
                    out_tokens.append(
                        jnp.argmax(logits, axis=-1).astype(jnp.int32))
                jax.block_until_ready(logits)
            decode_wall += time.perf_counter() - w0
            with ins.region("detokenize", nominal_cpi=1.0,
                            **{io_kw: 4.0 * args.batch * args.tokens}):
                # only this round's tokens: each window must measure one
                # round's work, not everything accumulated since round 0
                _ = np.concatenate(
                    [np.asarray(t) for t in out_tokens[-args.tokens:]], axis=1)
        assert rec.within_paper_budget()
        print(f"[round {rnd}] decoded {args.tokens}/req")
        if pipe is not None:
            pipe.submit_recorder(rec, label=f"round {rnd}")
        else:
            entry = session.ingest_recorder(rec, label=f"round {rnd}")
            if engine is not None:
                sync_actions += engine.observe(entry, session)
            on_window(entry)

    report = session.report() if pipe is None else pipe.close()
    if engine is not None:
        actions = pipe.take_actions() if pipe is not None else sync_actions
        print(f"[serve] policy decisions: {len(engine.log)} "
              f"({len(engine.log.fired())} fired, "
              f"{len(actions)} action(s) collected)")
    seqs = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"\n[serve] {cfg.name} (reduced, schema={args.schema}): "
          f"batch={args.batch} prompt={args.prompt_len} "
          f"decoded={args.rounds * args.tokens}")
    for b in range(min(args.batch, 2)):
        print(f"  request {b}: {seqs[b].tolist()}")
    print("\n" + report.render(tree))
    total = args.batch * args.rounds * args.tokens
    tput = total / max(decode_wall, 1e-9)
    dev = jax.devices()[0]
    print(f"\ndecode throughput: {tput:.1f} tok/s ({dev.platform}, "
          f"{dev.device_kind}, jnp path)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
