#!/usr/bin/env bash
# Serving smoke gate: the web-service sample's --self-test end to end
# on CPU — registry deploy + warmup, concurrent clients, a hot-swap
# mid-traffic with zero failed requests, a coherent /metrics, a traced
# request whose phases account for its span wall, and a Prometheus
# scrape round-tripped through the stdlib exposition parser
# (observability.metrics.parse_prometheus_text — an unparseable line
# fails the self-test, and the grep below keeps the scrape from being
# silently skipped).
#
# Runnable standalone (like check_collection.sh) and cheap enough for
# CI: one process, ~1 min on a cold CPU.  The timeout wrapper keeps a
# wedged dispatcher/server from hanging the gate forever.
#
# Two forced host devices make the run MULTI-REPLICA end to end: the
# registry deploys with replicas="all", so the self-test exercises the
# compile-once/place-everywhere path, the cross-replica scheduler, and
# the per-replica metrics — on plain CPU.
set -euo pipefail
cd "$(dirname "$0")/.."
out=$(timeout -k 10 300 env JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    python apps/web-service-sample/web_service.py --self-test)
printf '%s\n' "$out"
grep -q "prometheus scrape OK" <<<"$out" || {
    echo "smoke FAIL: self-test never scraped /metrics?format=prometheus" >&2
    exit 1
}
grep -q "trace check: " <<<"$out" || {
    echo "smoke FAIL: self-test never verified a request trace" >&2
    exit 1
}
grep -q "replica check: 2 replicas" <<<"$out" || {
    echo "smoke FAIL: self-test never verified multi-replica serving" >&2
    exit 1
}

# The rest of the serving stack, by the tests that hold each mechanism
# (tier-1 and the two `slow` ones that start real processes): elastic
# pool, hedging and priority admission; the decode engine; the
# executable store, in one process and across two; the pager; the fleet
# behind its router, with fake workers and with real ones; replica
# groups over sub-meshes.  No rate is asserted here or anywhere on a
# CPU: speed is `python3 benchmark/run.py` on the chip.
timeout -k 10 1500 env JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
    python -m pytest -q -p no:cacheprovider \
    tests/test_serving_elastic.py tests/test_serving_decode.py \
    tests/test_execstore.py tests/test_serving_pager.py \
    tests/test_fleet.py tests/test_tracefleet.py \
    tests/test_serving_shardgroup.py
echo "serving smoke OK"
