"""Generate the Grafana dashboard from the ACTUAL metrics registry.

The reference ships a hand-written dashboard
(``deployment/grafana/dashboards/main.json``); hand-written dashboards
drift. This generator imports the service modules (which register their
metrics in ``lzy_tpu.utils.metrics.REGISTRY``), then emits one panel per
metric with the idiomatic query shape per type:

- counter  -> ``sum(rate(<name>[5m])) by (labels)`` timeseries
- gauge    -> ``<name>`` timeseries
- histogram-> p50/p95 via ``histogram_quantile`` over bucket rates

Output: ``deploy/grafana/dashboard.json`` (committed; the suite asserts
it stays in sync — tests/test_deploy.py).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def registry_metrics():
    # importing the service modules registers every production metric
    import lzy_tpu.service.allocator  # noqa: F401
    import lzy_tpu.service.graph_executor  # noqa: F401
    import lzy_tpu.service.workflow_service  # noqa: F401
    import lzy_tpu.service.worker  # noqa: F401
    # serving plane: engine + KV cache + request queue panels
    import lzy_tpu.serving.engine  # noqa: F401
    import lzy_tpu.serving.kv_cache  # noqa: F401
    # tiered KV cache: demotions/promotions by (from_tier, to_tier),
    # host/storage occupancy, cross-replica imports + fallbacks
    # (lzy_kvtier_*; the index half lives in gateway/kv_index)
    import lzy_tpu.serving.kv_tier  # noqa: F401
    import lzy_tpu.serving.scheduler  # noqa: F401
    # speculative decoding: proposed/accepted, acceptance rate, tok/step,
    # draft truncations
    import lzy_tpu.serving.spec  # noqa: F401
    # native paged-attention kernels: dispatches by path, quantized
    # blocks resident, dequant-error EWMA (lzy_kernel_*)
    import lzy_tpu.ops.paged_attention  # noqa: F401
    # a model with routed experts and per-slot state: assignments, held
    # assignments, experts touched / held a decode round (lzy_moe_*);
    # state rows zeroed for a new request is the engine's (lzy_state_*)
    import lzy_tpu.models.nemotron_h  # noqa: F401
    # a latent (MLA) cache: cached positions the decode rounds' rows read
    # through the latent pool, and those rows, a layer (lzy_mla_*)
    import lzy_tpu.models.deepseek_v3  # noqa: F401
    # a model with window layers: keys read by kind of layer, a round
    import lzy_tpu.models.cohere2_moe  # noqa: F401
    # a model with Mamba-1 layers: live rows whose state a round moved
    import lzy_tpu.models.jamba  # noqa: F401
    # a model with a carried window: live rows whose window a round moved
    import lzy_tpu.models.zaya  # noqa: F401
    # a model that chooses its key blocks: blocks visible and read, rows
    # that chose and rows served densely, rows whose lightning state moved
    import lzy_tpu.models.minicpm_sala  # noqa: F401
    # a model of power-retention layers: rows whose state a round moved
    import lzy_tpu.models.brumby  # noqa: F401
    # a model that runs its layers several times a token: real rows of
    # decode rounds and the pass the head read, summed (lzy_loop_*)
    import lzy_tpu.models.ouro  # noqa: F401
    # the latent indexer's counts (what the selecting layers saw, chose
    # and read; the latent window's reads)
    import lzy_tpu.models.dots3_note  # noqa: F401
    # a model of several residual streams and differential heads: rows x
    # sublayers mixed, the noise heads' weight and the reads it weighs
    import lzy_tpu.models.motif  # noqa: F401
    # a router wider than its experts with weights: the choices that fell
    # on identity experts and the weight they carried
    import lzy_tpu.models.longcat_flash  # noqa: F401
    # sharded gang replicas: gang size by mesh, per-shard KV blocks,
    # shard-skew tripwire, whole-gang failovers (lzy_sharded_*)
    import lzy_tpu.serving.sharded.metrics  # noqa: F401
    # multi-tenant SLO: per-tenant requests/tokens/TTFT, queue depth,
    # KV blocks, rate-bucket levels, sheds (lzy_tenant_*)
    import lzy_tpu.serving.tenancy  # noqa: F401
    # streaming delivery: frames by kind, wire resumes, cancels by
    # phase, consumer-stall seconds, slow-consumer sheds, live sessions
    # (lzy_stream_*)
    import lzy_tpu.serving.streams  # noqa: F401
    # gateway: routing hit rate, failovers, autoscale, per-replica load
    import lzy_tpu.gateway.fleet  # noqa: F401
    import lzy_tpu.gateway.kv_index  # noqa: F401
    import lzy_tpu.gateway.router  # noqa: F401
    import lzy_tpu.gateway.service  # noqa: F401
    # control-plane crash recovery: journal appends/degraded, gang
    # adoptions, fence resubmits, orphaned requests, recovery latency
    # (lzy_gwreco_*)
    import lzy_tpu.gateway.journal  # noqa: F401
    import lzy_tpu.gateway.recovery  # noqa: F401
    # disagg: transfer bytes/latency, cache-skips, re-prefill fallbacks
    import lzy_tpu.gateway.disagg  # noqa: F401
    import lzy_tpu.serving.disagg.decode  # noqa: F401
    import lzy_tpu.serving.disagg.prefill  # noqa: F401
    # robustness: chaos faults injected, circuit breaker state, shed
    # requests (lzy_chaos_* / lzy_breaker_* / lzy_shed_*)
    import lzy_tpu.chaos.faults  # noqa: F401
    import lzy_tpu.gateway.health  # noqa: F401
    # workflow-native inference: generations, cached hits, stream
    # resumptions, conversation affinity (lzy_llm_*)
    import lzy_tpu.llm.metrics  # noqa: F401
    # load plane: trace-replay requests/retries, virtual-time TTFT and
    # inter-token histograms, replay speedup, shed rate (lzy_load_*)
    import lzy_tpu.load.driver  # noqa: F401
    from lzy_tpu.utils.metrics import Counter, Gauge, Histogram, REGISTRY

    kinds = {Counter: "counter", Gauge: "gauge", Histogram: "histogram"}
    out = []
    for name, metric in sorted(REGISTRY._metrics.items()):
        out.append({
            "name": name,
            "type": kinds.get(type(metric), "gauge"),
            "help": getattr(metric, "help", "") or getattr(
                metric, "_help", ""),
        })
    return out


def _panel(metric: dict, idx: int) -> dict:
    name, kind = metric["name"], metric["type"]
    if kind == "counter":
        targets = [{"expr": f"sum(rate({name}[5m]))",
                    "legendFormat": f"{name}/s"}]
        title = f"{name} (rate)"
    elif kind == "histogram":
        targets = [
            {"expr": ("histogram_quantile(0.50, "
                      f"sum(rate({name}_bucket[5m])) by (le))"),
             "legendFormat": "p50"},
            {"expr": ("histogram_quantile(0.95, "
                      f"sum(rate({name}_bucket[5m])) by (le))"),
             "legendFormat": "p95"},
        ]
        title = f"{name} (p50/p95)"
    else:
        targets = [{"expr": name, "legendFormat": name}]
        title = name
    return {
        "id": idx + 1,
        "title": title,
        "description": metric["help"],
        "type": "timeseries",
        "datasource": {"type": "prometheus", "uid": "${datasource}"},
        "targets": [{"refId": chr(ord("A") + i), **t}
                    for i, t in enumerate(targets)],
        "gridPos": {"h": 8, "w": 12, "x": 12 * (idx % 2),
                    "y": 8 * (idx // 2)},
        "fieldConfig": {"defaults": {"unit": "short"}, "overrides": []},
    }


def build() -> dict:
    metrics = registry_metrics()
    return {
        "title": "lzy-tpu control plane",
        "uid": "lzy-tpu-main",
        "schemaVersion": 39,
        "tags": ["lzy-tpu"],
        "time": {"from": "now-6h", "to": "now"},
        "refresh": "30s",
        "templating": {"list": [{
            "name": "datasource", "type": "datasource",
            "query": "prometheus", "label": "datasource",
        }]},
        "panels": [_panel(m, i) for i, m in enumerate(metrics)],
        "_generated_from": sorted(m["name"] for m in metrics),
    }


def main() -> int:
    out_path = os.path.join(REPO, "deploy", "grafana", "dashboard.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(build(), f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
