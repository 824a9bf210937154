"""Serving CLI: batched generation on a host mesh, with the optional BMO-NN
kNN-LM retrieval hook (the paper's technique in the serving path).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --smoke \
      --batch 4 --prompt-len 16 --new-tokens 32 --knn-lm

Retrieval is served from a persistent ``repro.api.Index`` handle.
``--index-dir`` reuses a saved index across launches (build-once/serve-many:
loaded when present, built+saved when not — the next-token payload rides the
handle's sidecar); ``--index-append`` grows the datastore during decode;
``--index-shards`` spans the index over a mesh, and a saved index re-shards
on the way in when the flag differs from the saved shard count;
``--tune`` self-races kernel/frontier configs after build/load
(``repro.tune``, DESIGN.md §9) and persists the winner with the index.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.serve.engine import KNNLMConfig, ServeEngine
from repro.sharding.spec import init_params
from repro.utils import get_logger
from repro.utils.compile_cache import use_compile_cache

log = get_logger("repro.serve")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=None)
    ap.add_argument("--knn-lm", action="store_true")
    ap.add_argument("--index-dir", default=None,
                    help="load the retrieval IndexStore from this directory "
                         "if it exists, else build it there once")
    ap.add_argument("--index-append", action="store_true",
                    help="insert each decode step's (hidden, token) pairs "
                         "back into the index")
    ap.add_argument("--index-shards", type=int, default=0,
                    help=">1: span the retrieval index over that many mesh "
                         "devices (one ShardedIndexStore, DESIGN.md §5); "
                         "needs that many visible devices — on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--tune", action="store_true",
                    help="autotune the retrieval index after build/load: "
                         "race kernel/frontier candidate configs on measured "
                         "wall time (repro.tune, DESIGN.md §9) and serve the "
                         "winner; with --index-dir the tuned.json sidecar is "
                         "persisted next to the checkpoint so later launches "
                         "serve tuned without re-racing")
    ap.add_argument("--fleet-root", default=None, metavar="DIR",
                    help="serve retrieval from a namespace fleet rooted "
                         "here (repro.fleet, DESIGN.md §11): the index "
                         "becomes the fleet's 'default' namespace "
                         "(created on first launch, recovered from the "
                         "manifest afterwards) and the engine shares the "
                         "fleet's request plane; overrides --index-dir")
    ap.add_argument("--max-resident", type=int, default=8,
                    help="with --fleet-root: LRU residency budget — "
                         "namespaces beyond this many are checkpointed "
                         "and evicted, reloading transparently on access")
    ap.add_argument("--datastore-size", type=int, default=2048)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--autoscale", action="store_true",
                    help="consult a ScalePolicy on the request-plane "
                         "telemetry after serving and LOG its "
                         "add_replicas/reshard recommendation "
                         "(recommendation-only unless --autoscale-apply)")
    ap.add_argument("--autoscale-apply", action="store_true",
                    help="actually apply an add_replicas recommendation "
                         "to the live handle (reshard stays advisory)")
    ap.add_argument("--audit-rate", type=float, default=0.0,
                    help="shadow δ-audit: re-answer this fraction of "
                         "certified tickets exactly, off the critical path, "
                         "and compare against the served ids "
                         "(repro.obs.audit, DESIGN.md §10)")
    ap.add_argument("--audit-dir", default=None, metavar="DIR",
                    help="write a replayable flight-recorder bundle here "
                         "for every audited mismatch "
                         "(replay with tools/replay_audit.py)")
    ap.add_argument("--slo", action="store_true",
                    help="evaluate burn-rate SLOs (recall vs δ, shed rate) "
                         "over the plane's telemetry after serving; a "
                         "burning recall SLO engages the recall guard "
                         "(fallback to untuned, flag a re-tune) when "
                         "--autoscale-apply is set, else it is logged")
    ap.add_argument("--health-dump", default=None, metavar="PATH",
                    help="write the combined health snapshot (stats + "
                         "audit + SLO state) here on exit as JSON")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="write the obs metrics registry here on exit "
                         "(.json = JSON snapshot, else Prometheus text)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the raw trace-event dump here on exit "
                         "(render/convert with tools/trace_view.py)")
    args = ap.parse_args(argv)
    use_compile_cache()

    entry = get_arch(args.arch)
    cfg = entry.smoke if args.smoke else entry.config
    assert cfg.family in ("dense",) or not args.knn_lm, \
        "kNN-LM hook needs a hidden-state-exposing DenseLM"
    plan = dataclasses.replace(entry.plan, fsdp=False, sp=False, ep=False,
                               tp=args.model > 1)
    model = build_model(cfg)
    mesh = make_host_mesh(args.data, args.model)
    rng = jax.random.PRNGKey(0)
    params = init_params(model.param_specs(), rng)
    max_seq = args.max_seq or (args.prompt_len + args.new_tokens + 8)

    knn_cfg = index = fleet = fleet_plane = None
    if args.knn_lm:
        import os

        from repro.api import Index
        from repro.configs.base import BMOConfig
        ds_rng = np.random.default_rng(0)
        keys = ds_rng.normal(size=(args.datastore_size, cfg.d_model)).astype(np.float32)
        next_ids = ds_rng.integers(0, cfg.vocab_size, args.datastore_size).astype(np.int32)
        from repro.serve.plane import PlaneConfig
        knn_cfg = KNNLMConfig(lam=0.2, index_shards=args.index_shards,
                              bmo=BMOConfig(
            k=8, delta=0.05, block=min(64, cfg.d_model), batch_arms=16),
                              plane=PlaneConfig(audit_rate=args.audit_rate,
                                                audit_dir=args.audit_dir))
        policies = dict(cache=knn_cfg.cache_policy(),
                        compaction=knn_cfg.compaction_policy())
        shards = max(args.index_shards, 1)
        if args.fleet_root:
            from repro.fleet import Fleet, FleetConfig
            fleet = Fleet(args.fleet_root,
                          FleetConfig(max_resident=args.max_resident))
            if "default" in fleet:
                index = fleet.get("default")
                log.info("fleet %s: recovered namespace 'default' "
                         "(%d live slots, %d shard(s); %d namespace(s) "
                         "total, %d resident)", args.fleet_root,
                         index.n_live, index.n_shards, len(fleet),
                         fleet.resident_count)
            else:
                index = fleet.create("default", keys, knn_cfg.bmo,
                                     jax.random.PRNGKey(7), shards=shards,
                                     payload=next_ids)
                log.info("fleet %s: created namespace 'default' "
                         "(%d shard(s))", args.fleet_root, index.n_shards)
            # default= binds the 'default' namespace as the plane's default
            # index so the δ-auditor (--audit-rate) covers its traffic
            fleet_plane = fleet.serve(knn_cfg.plane, default="default")
        elif args.index_dir and os.path.exists(args.index_dir):
            # one call covers both layouts; --index-shards != saved shard
            # count re-shards on the way in, the payload sidecar rides the
            # remap inside the handle
            index = Index.load(args.index_dir,
                               shards=shards if shards > 1 else None,
                               **policies)
            if index.payload is None:
                if index.sharded:
                    # a sharded store's live global ids are non-contiguous,
                    # so this CLI's row-ordered next_ids CANNOT be attached
                    # slot-aligned — even when the lengths happen to match,
                    # every neighbour would vote the wrong token
                    raise FileNotFoundError(
                        f"{args.index_dir} holds a sharded index but no "
                        "payload.npy sidecar (the slot-aligned next-token "
                        "ids Index.save writes when a payload is attached) "
                        "— rebuild with this CLI or add the sidecar")
                index.attach_payload(next_ids)
            log.info("loaded index from %s (%d live slots, %d shard(s))",
                     args.index_dir, index.n_live, index.n_shards)
        else:
            index = Index.build(keys, knn_cfg.bmo, jax.random.PRNGKey(7),
                                shards=shards, payload=next_ids, **policies)
            if args.index_dir:
                index.save(args.index_dir)
                log.info("built + saved index to %s (%d shard(s))",
                         args.index_dir, index.n_shards)
        if args.tune and index.tuned is None:
            t0 = time.time()
            report = index.tune(rng=jax.random.PRNGKey(13))
            log.info("autotuned in %.1fs: %s (winner %.2f ms vs default "
                     "%.2f ms over %d raced candidates)",
                     time.time() - t0, report["config"],
                     report.get("winner_median_ms", float("nan")),
                     report.get("default_median_ms", float("nan")),
                     report.get("raced", 0))
            if args.index_dir:
                from repro.tune import save_tuned, signature_of
                save_tuned(args.index_dir, signature_of(index.store),
                           index.tuned,
                           measured={"epoch_ms": index.tuned.epoch_ms,
                                     "round_ms": index.tuned.round_ms})
                log.info("tuned.json sidecar -> %s", args.index_dir)
        elif args.tune:
            log.info("index loaded with a tuned sidecar — serving it "
                     "without re-racing (%s)", index.tuned.to_dict())

    engine = ServeEngine(model, params, plan, mesh, batch_size=args.batch,
                         max_seq=max_seq, knn_lm=knn_cfg,
                         index=index, index_append=args.index_append,
                         plane=fleet_plane,
                         plane_namespace="default" if fleet_plane else None)
    prompts = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.time()
    out, retrieval_ops = engine.generate(prompts, args.new_tokens)
    dt = time.time() - t0
    log.info("generated %s tokens in %.2fs (%.1f tok/s)%s",
             out.shape, dt, out.size / dt,
             f"; retrieval coord-ops={retrieval_ops:.0f}" if args.knn_lm else "")
    if args.knn_lm:
        if (args.audit_rate > 0.0 and engine.plane is not None
                and engine.plane.auditor is not None):
            done = engine.plane.audit_flush()   # oracle runs post-serve
            a = engine.plane.auditor.summary()
            log.info("δ-audit: %d ticket(s) flushed — %d/%d audited rows "
                     "mismatched, err_upper=%.4g (%s), %d bundle(s)",
                     done, a["mismatch_rows"], a["sampled_rows"],
                     a["err_upper"], a["method"], len(a["bundles"]))
            for b in a["bundles"]:
                log.warning("flight-recorder bundle: %s", b)
        st = engine.stats            # typed repro.api.ServeStats (schema v2)
        log.info("engine stats: %s", st.as_dict())
        if st.shard_coord_ops is not None:
            log.info("per-shard coord-ops %s, max rounds %s",
                     [f"{v:.3g}" for v in st.shard_coord_ops],
                     st.shard_rounds)
        if fleet is not None:
            fleet.flush()       # manifest + dirty checkpoints to disk
            log.info("fleet stats: %s", fleet.stats())
        if args.autoscale:
            from repro.serve.scale import QueueDepthPolicy
            policy = QueueDepthPolicy(sustain=1)
            decision = policy.recommend(st)
            log.info("autoscale recommendation: %s value=%d (%s)",
                     decision.action, decision.value,
                     decision.reason or "no signal")
            if (args.autoscale_apply and decision.action == "add_replicas"
                    and engine.index is not None):
                engine.index.add_replicas(decision.value)
                log.info("applied: read fan-out now %d replicas",
                         engine.stats.replicas)
        if args.slo and engine.plane is not None:
            from repro.obs import (AlertSink, SLOEngine, default_slos,
                                   plane_sources)
            from repro.serve.scale import RecallGuardPolicy, apply_guard
            plane = engine.plane
            delta = float(engine.index.cfg.delta)
            sink = AlertSink()
            slo = SLOEngine(default_slos(delta), sink=sink, obs=plane.obs)
            slo.observe(plane_sources(plane, plane.auditor))
            state = slo.state()
            for s in state["slos"]:
                burning = any(r["active"] for r in s["rules"])
                log.info("SLO %s: bad_frac=%.4g budget=%g %s", s["name"],
                         s["bad_frac"], s["budget"],
                         "BURNING" if burning else "ok")
            guard = RecallGuardPolicy(sink)
            decision = guard.recommend(engine.stats)
            log.info("recall guard: %s (%s)", decision.action,
                     decision.reason or "no signal")
            if args.autoscale_apply and apply_guard(engine.index, decision):
                log.info("applied: serving_fallback=%s retune_requested=%s",
                         engine.index.serving_fallback,
                         engine.index.retune_requested)
    if args.health_dump:
        from repro.obs import dump_health
        dump_health(args.health_dump, plane=engine.plane,
                    index=engine.index)
        log.info("health snapshot -> %s", args.health_dump)
    if args.metrics_dump or args.trace:
        from repro.obs import dump_events, dump_metrics, get_obs
        obs = get_obs()
        if args.metrics_dump:
            dump_metrics(args.metrics_dump, obs)
            log.info("metrics dumped to %s", args.metrics_dump)
        if args.trace:
            dump_events(args.trace, obs)
            log.info("trace dumped to %s (%d events, %d dropped)",
                     args.trace, obs.events.total, obs.events.drops)
    print(out[:, :16])


if __name__ == "__main__":
    main()
