# Build/test entry points (reference counterpart: /root/reference/makefile).

# native: build the C++ batch verifier shared object
native:
	python -c "from babble_tpu import native_crypto; assert native_crypto.available(), 'native build failed'"

tests: test

test:
	python -m pytest tests/ -q

# flagtest: version-flag purity — FLAG must be empty on release branches
# (reference: make flagtest -> TestFlagEmpty)
flagtest:
	BABBLE_FLAGTEST=1 python -m pytest tests/test_version.py -q

# extratests: the long churn-storm suite by itself
# (reference: make extratests -> -run Extra)
extratests:
	python -m pytest tests/test_node_churn.py -q

alltests: test

# multi-chip sharding dry run on a virtual 8-device CPU mesh
dryrun:
	JAX_PLATFORMS=cpu python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# chaossmoke: short-budget nemesis soak — 10% drop + duplication +
# partition/heal on a 5-node in-mem cluster, plus the bounded
# shutdown/leave-under-partition checks; deterministic under
# BABBLE_CHAOS_SEED (docs/robustness.md). The full nemesis storm
# (flapper + slow peer, more rounds) stays behind -m slow.
# BABBLE_LOCKCHECK=1 arms the runtime lock-order recorder
# (common/lockcheck.py): the soak's real thread interleavings validate
# the babblelint static lock graph — the soak asserts zero inversions.
chaossmoke:
	JAX_PLATFORMS=cpu BABBLE_CHAOS_SEED=42 BABBLE_LOCKCHECK=1 python -m pytest tests/test_chaos.py -q -m "chaos and not slow"

# chaossoak: the long storm, seed overridable for exploratory runs
chaossoak:
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q -m "chaos"

# byzsmoke: short seeded honest-vs-Byzantine soak — 4 honest + 1
# equivocating node under chaos drop; asserts identical honest chains
# past the attack window, quarantine with a verifiable equivocation
# proof, proof persistence across --store --bootstrap restart, and
# receiving-side sync_limit caps (docs/robustness.md §Byzantine fault
# model). The f=⌊(N−1)/3⌋ storm stays behind -m slow.
byzsmoke:
	JAX_PLATFORMS=cpu BABBLE_CHAOS_SEED=42 python -m pytest tests/test_byzantine.py -q -m "byz and not slow"

# byzstorm: the full storm (two simultaneous adversaries under chaos)
byzstorm:
	JAX_PLATFORMS=cpu python -m pytest tests/test_byzantine.py -q -m "byz"

# metricslint: the instrument catalog and the docs table must match in
# both directions (a new instrument cannot ship undocumented). Now a
# thin shim over the babblelint metrics pass (docs/static_analysis.md).
metricslint:
	python -m babble_tpu.obs.lint docs/observability.md

# staticcheck: babblelint, the project-wide static-analysis suite
# (docs/static_analysis.md) — clock/RNG discipline, lock discipline,
# knob drift, metrics drift, with self-linted inline allows. Then prove
# its teeth: --self-proof injects one violation per
# pass (plus a stale allow) and exits nonzero unless EVERY pass fires,
# so a toothless linter fails the build, not the code it guards.
staticcheck:
	python -m babble_tpu.analysis
	python -m babble_tpu.analysis --self-proof

# healthsmoke: cluster healthview end to end — a live 4-node cluster
# with HTTP services merged over /metrics + /stats + /suspects; asserts
# every node up and healthy, per-node lag + advance rates, and the
# commit-p50-vs-500ms SLO scored (docs/observability.md §Cluster
# healthview); plus the merge math + sim-export unit coverage
healthsmoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_healthview.py -q -m "not slow"

# tracesmoke: cross-node causal tracing end to end — a live 4-node TCP
# cluster with HTTP services, every tx sampled; asserts a committed
# transaction's /trace/<txid> records merge (traceview) into a timeline
# with >= 2 gossip hops and monotone stamps, per-hop wire/queue/insert/
# consensus attribution present, plus the wire backward-compat and
# flight-recorder paths (docs/observability.md §Causal tracing)
tracesmoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_trace.py -q -m "not slow"

# clientsmoke: light-client gateway tier end to end (docs/clients.md) —
# a live 4-validator TCP cluster with one sharded gateway and a
# 100-subscriber swarm: every sampled accepted tx's GET /proof/<txid>
# verifies OFFLINE from the validator set alone, pushed blocks arrive
# in order with zero gaps on healthy subscribers, and a deliberately
# stalled subscriber is shed without raising anyone else's push
# latency; plus the adversarial proof/checkpoint unit coverage.
clientsmoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_client.py -q -m "not slow"

# prunesmoke: lifecycle tier end to end (docs/lifecycle.md) — pruned-vs-
# oracle digest equality in virtual time, the rotation/rejoin-from-
# pruned-checkpoint sim, the behind_retention HTTP slug, evidence
# surviving compaction, SQLite shrink+vacuum mechanics, and a LIVE
# 4-validator cluster where every node prunes mid-traffic, one rotates
# out through consensus, and a fresh validator joins by fast-syncing
# from peers that have all compacted their history.
prunesmoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_lifecycle.py -q -m "not slow"

# killtestnet: reap stray demo/testnet.py processes from an aborted run
# — they squat the demo ports and starve whatever runs next. The
# well-known pidfile covers even a SIGKILLed driver; each recorded PID
# is verified against /proc/<pid>/cmdline before any signal, so a PID
# the OS recycled to an unrelated process is never touched. The pattern
# sweep catches nodes whose pidfile was lost.
killtestnet:
	-@if [ -f /tmp/babble_tpu_testnet.pids ]; then for sig in TERM KILL; do sort -u /tmp/babble_tpu_testnet.pids | while read pid; do if grep -aq babble_tpu "/proc/$$pid/cmdline" 2>/dev/null; then kill -$$sig -- -$$pid 2>/dev/null; kill -$$sig $$pid 2>/dev/null; fi; done; [ $$sig = TERM ] && sleep 1 || true; done; rm -f /tmp/babble_tpu_testnet.pids; echo "killtestnet: pidfile reaped"; fi
	-@pkill -9 -f "[b]abble_tpu.cli (run|dummy|signal)" 2>/dev/null; true
	-@pkill -9 -f "[b]abble_tpu.client.gateway" 2>/dev/null; true
	@echo "killtestnet: done"

# simsmoke: deterministic virtual-time scenario sweep — 200 seeded
# chaos x byzantine x churn x overload combinations with invariant
# checks (no fork / liveness after heal / bounded queues / exactly-once
# commit), in well under a minute of wall time (docs/simulation.md).
# Asserts zero violations, then proves the failure path end-to-end: an
# injected failing invariant must shrink to a minimal reproducer
# artifact that replays byte-identically.
# BABBLE_LOCKCHECK=1: the sweep doubles as the sim-side lock-order
# audit (docs/static_analysis.md §Lock model) — zero inversions asserted.
simsmoke:
	JAX_PLATFORMS=cpu BABBLE_LOCKCHECK=1 python -m babble_tpu.sim.sweep --seeds 200 --out sim_artifacts | tail -n 1 | python -c "import json,sys; d=json.loads(sys.stdin.read().strip()); assert d['sim_scenarios'] >= 200, d; assert d['failed'] == 0, d; assert d.get('lock_inversions', 0) == 0, d; print('simsmoke ok:', d['sim_scenarios'], 'scenarios,', d['blocks_committed'], 'blocks,', str(d['speedup_virtual']) + 'x virtual speedup,', d['wall_s'], 's,', d.get('lock_order_edges', 0), 'lock edges, 0 inversions')"
	rm -rf sim_artifacts_inject  # stale artifacts would break the ls-pick below after a generator change
	JAX_PLATFORMS=cpu python -m babble_tpu.sim.sweep --seeds 1 --inject-failure --out sim_artifacts_inject | tail -n 1 | python -c "import json,sys,glob; d=json.loads(sys.stdin.read().strip()); assert d['failed'] == 1 and d['shrunk'] == 1 and d['artifacts'], d; print('shrink ok:', d['artifacts'][0])"
	JAX_PLATFORMS=cpu python -m babble_tpu.sim.sweep --replay $$(ls sim_artifacts_inject/repro_*.json | head -n 1) | python -c "import json,sys; d=json.loads(sys.stdin.read().strip()); assert d['digests_match'] and d['violations'], d; print('replay ok: digests match')"

# simsweep: the full thousands-of-seeds sweep (exploratory / nightly)
simsweep:
	JAX_PLATFORMS=cpu python -m babble_tpu.sim.sweep --seeds 2000 --out sim_artifacts

# wheel: build the release wheel (native lib bundled+precompiled); the
# analogue of the reference's scripts/dist.sh release build
wheel:
	python -m pip wheel . --no-deps -w dist

.PHONY: native tests test flagtest extratests alltests dryrun chaossmoke chaossoak byzsmoke byzstorm metricslint staticcheck healthsmoke tracesmoke clientsmoke prunesmoke killtestnet simsmoke simsweep wheel
