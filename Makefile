# cedar_tpu build/test/demo targets (role parity with the reference
# Makefile: build, test, schema generation, policy validation/formatting,
# kind demo wiring).

PYTHON ?= python
IMAGE ?= cedar-tpu-webhook:latest
# Recorded OpenAPI fixtures for full-schema generation. Defaults to the
# mounted reference snapshot; point FIXTURES at any directory of
# <api>.schema.json/<api>.resourcelist.json recordings (or at a live
# cluster's recordings) elsewhere.
# ":"-separated fixture directories: the reference's four recorded groups
# (core/apps/authentication/rbac) + this repo's generated fixtures for the
# remaining API groups (tools/gen_openapi_fixtures.py)
FIXTURES ?= /root/reference/internal/schema/convert/testdata:tests/testdata/openapi
CERT_DIR ?= mount/certs

.PHONY: all
all: native test

##@ Build

.PHONY: native
native: ## Compile the C++ SAR fast-path encoder
	$(PYTHON) -c "from cedar_tpu.native.build import ensure_built; print(ensure_built())"

.PHONY: image
image: ## Build the webhook container image
	docker build -t $(IMAGE) .

##@ Test

.PHONY: test
test: ## Run the unit + differential test suite (virtual CPU devices; chaos/slow excluded — see `make chaos`)
	$(PYTHON) -m pytest tests/ -q -m "not slow"

.PHONY: chaos
chaos: ## Run the fault-injection resilience suite deterministically (seeded scenarios, cpu backend)
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_resilience.py -q -m chaos

.PHONY: gameday
gameday: ## Run a scripted chaos game day (cedar-chaos) against a locally spawned server; SCENARIO=kill-decode|device-loss|poison-crd|store-stall|replica-loss
	JAX_PLATFORMS=cpu $(PYTHON) -m cedar_tpu.cli.chaos --spawn \
	    --scenario $${SCENARIO:-kill-decode}

.PHONY: chip-smoke
chip-smoke: ## The chip check: serve the 10k-policy webhook from one TPU chip and compare every answer with the interpreter (chip_smoke.py; one process holds the chip)
	$(PYTHON) chip_smoke.py

.PHONY: bench
bench: ## Run the headline benchmark on the TPU (exits nonzero with a JSON tail when JAX finds none; JAX_PLATFORMS=cpu runs the cpu plane by name)
	$(PYTHON) bench.py

.PHONY: bench-cache
bench-cache: ## Decision-cache microbenchmark: Zipf SAR replay, hit ratio + cached-path p50/p99 vs the batched engine (cpu)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --cache

.PHONY: bench-pipeline
bench-pipeline: ## Pipelined vs serial engine: decisions/sec + lone-request p50/p99 on one policy set (cpu; docs/performance.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --pipeline

.PHONY: bench-steady
bench-steady: ## Persistent serving loop: e2e >=80% of device-resident rate (hardware), >1 batch in flight + staging occupancy overlap, AOT cold-start-to-warm with zero fresh traces, 1152-body on/off byte differential (TPU, or exits nonzero when JAX finds none; JAX_PLATFORMS=cpu runs the cpu plane with the hardware gates reported as skipped; docs/performance.md)
	$(PYTHON) bench.py --steady

.PHONY: bench-shadow
bench-shadow: ## Shadow-rollout overhead: live p50/p99 + saturated throughput at 0/10/100% shadow sampling (cpu; docs/rollout.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --shadow

.PHONY: bench-chaos
bench-chaos: ## Game-day suite incl. replica-loss: availability/correctness/recovery SLOs under scripted faults + chaos-disabled differential (cpu; docs/resilience.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --chaos

.PHONY: bench-encode
bench-encode: ## Host-side budget: native encode µs/req at 1/2/4 threads, packed-vs-per-chunk decode, 3.5µs encode regression gate (cpu; docs/performance.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --encode

.PHONY: bench-scale
bench-scale: ## Giant policy sets: 10k vs 100k serving-rate ratio, single-edit incremental recompile <1s + zero-fresh-trace gate (cpu; docs/performance.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --scale

.PHONY: bench-coverage
bench-coverage: ## Lowerability burn-down gate: full-vs-legacy compiler coverage % on the adversarial corpus (strictly higher + pinned floor), per-family fallback-vs-device serving ratio (cpu; docs/lowering.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --coverage

.PHONY: bench-tenant
bench-tenant: ## Multi-tenant shared plane: 1 vs 10 fused tenants on one device — zero cross-tenant decision flips, per-tenant p99 budget, tenant-scoped dirty shards (cpu; docs/multitenancy.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --tenants

.PHONY: bench-fleet
bench-fleet: ## Engine-fleet scaling: decisions/sec + lone p99 at 1/2/4 replicas, scaling-efficiency JSON (cpu; docs/fleet.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --fleet

.PHONY: bench-fanout
bench-fanout: ## Cross-process worker tier: 1/2/4 spawned workers, scaling + zero-flip differential + cross-worker cache hit gate + barrier swap (cpu; docs/fleet.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --fanout

.PHONY: bench-pod
bench-pod: ## Multi-host pod tier: 1/2/4 simulated hosts (spawned processes, gloo CPU collectives) — capacity refused@1/served@4, zero-flip differential vs single-host oracle, owner-only dirty re-upload with zero fresh traces, data-axis scaling reported (cpu; docs/fleet.md)
	$(PYTHON) bench.py --pod

.PHONY: bench-storm
bench-storm: ## Open-loop overload: 5x sustained storm — high-priority availability >=99.9% within budget, exact shed accounting, >=1 adaptive-tuner move, no-overload byte parity (cpu; docs/performance.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --storm

.PHONY: bench-mesh
bench-mesh: ## Mixed-protocol PDP: Zipf SAR + ext_authz + batch streams on ONE plane — zero decision flips vs the interpreter oracle, >=1 three-protocol coalesced tick, ext_authz p99 within budget (cpu; docs/pdp.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --mesh-traffic

.PHONY: bench-lifecycle
bench-lifecycle: ## Declarative lifecycle fleet: staggered tenant rollouts under storm traffic — zero-touch auto-promotion, halt+rollback at each gate tier, zero live flips, crash-mid-canary resume (cpu; docs/rollout.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --lifecycle

.PHONY: bench-analyze
bench-analyze: ## Device-exact policy-space analysis: 10k-rule universe sweep through the rule-bitset kernel (zero dead rules, zero oracle disagreements), exact one-edit semantic diff, lifecycle analyze gate halt+rollback with zero live flips (cpu; docs/analysis.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --analyze

.PHONY: bench-explain
bench-explain: ## Explain-plane pay-for-use: explain-off p99/throughput parity gate, explain-on cost + lazy compiles (cpu; docs/explainability.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --explain

.PHONY: bench-trace
bench-trace: ## Observability-plane pay-for-use: unsampled-tracing parity gate + byte differential, 100%-sampled cost (cpu; docs/observability.md)
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --trace

.PHONY: fuzz-soak
fuzz-soak: ## Differential fuzz soak over fresh seed ranges (cpu backend)
	for m in single multitier admission mutate mutate-adm; do \
	    $(PYTHON) tools/fuzz_soak.py --mode $$m --start $${START:-200000} --count $${COUNT:-300}; \
	done

.PHONY: graft-check
graft-check: ## Compile-check the jittable entry + multi-chip dry run
	$(PYTHON) __graft_entry__.py

##@ Static analysis

# the whole package: the hand-picked subdirectory list silently left
# server/stores/schema/apis/cli/entities/rbac un-linted
LINT_SCOPE ?= cedar_tpu

.PHONY: lint
lint: ## ruff + mypy over $(LINT_SCOPE) (missing tools are skipped with a note)
	@if $(PYTHON) -m ruff --version >/dev/null 2>&1; then \
	  $(PYTHON) -m ruff check --select E9,F $(LINT_SCOPE); \
	else \
	  echo "ruff not installed — falling back to compileall syntax check"; \
	  $(PYTHON) -m compileall -q $(LINT_SCOPE); \
	fi
	@if $(PYTHON) -m mypy --version >/dev/null 2>&1; then \
	  $(PYTHON) -m mypy --ignore-missing-imports --follow-imports=silent \
	    $(LINT_SCOPE); \
	else echo "mypy not installed — skipping (pip install mypy)"; fi

.PHONY: analyze
analyze: ## Whole-policy-set static analysis over the demo + test corpora (cedar-analyze --check)
	$(PYTHON) -m cedar_tpu.cli.analyze --check demo/authorization-policy.yaml
	$(PYTHON) -m cedar_tpu.cli.analyze --check demo/admission-policy.yaml
	$(PYTHON) -m cedar_tpu.cli.analyze --check tests/testdata/rbac
	$(PYTHON) -m cedar_tpu.cli.analyze --check tests/testdata/lifecycle/live
	$(PYTHON) -m cedar_tpu.cli.analyze --check tests/testdata/lifecycle/candidate
	$(PYTHON) -m cedar_tpu.cli.analyze --semantic-diff --check --flip-budget 1 \
	    tests/testdata/lifecycle/live --candidate tests/testdata/lifecycle/candidate

.PHONY: static
static: lint analyze ## The full static gate: lint + policy-set analysis

##@ Schema & policies

.PHONY: generate-schemas
generate-schemas: ## Regenerate cedarschema/ artifacts
	@for d in $$(echo "$(FIXTURES)" | tr ':' ' '); do \
	  test -d $$d || { \
	    echo "fixture dir $$d not found; point FIXTURES at ':'-separated" \
	         "directories of <api>.schema.json/<api>.resourcelist.json"; \
	    exit 1; }; \
	done
	$(PYTHON) -m cedar_tpu.cli.schema_generator --no-admission \
	    --format cedarschema --output cedarschema/k8s-authorization.cedarschema
	$(PYTHON) -m cedar_tpu.cli.schema_generator --no-admission \
	    --format json --output cedarschema/k8s-authorization.cedarschema.json
	$(PYTHON) -m cedar_tpu.cli.schema_generator --openapi-dir $(FIXTURES) \
	    --format cedarschema --output cedarschema/k8s-full.cedarschema
	$(PYTHON) -m cedar_tpu.cli.schema_generator --openapi-dir $(FIXTURES) \
	    --format json --output cedarschema/k8s-full.cedarschema.json

.PHONY: validate-policies
validate-policies: ## Validate every .cedar file against the full schema
	$(PYTHON) -m cedar_tpu.cli.validator \
	    --schema cedarschema/k8s-full.cedarschema.json \
	    $$(find . -name '*.cedar' -not -path './.git/*')

.PHONY: format-policies
format-policies: ## Canonicalize .cedar policy files in place (goldens excluded; commented files skipped)
	$(PYTHON) -m cedar_tpu.cli.policy_formatter \
	    $$(find demo mount -name '*.cedar' 2>/dev/null)

.PHONY: convert-rbac
convert-rbac: ## Convert the cluster's RBAC to Cedar (needs kubeconfig)
	$(PYTHON) -m cedar_tpu.cli.converter clusterrolebindings --output cedar

##@ Demo

.PHONY: demo-server
demo-server: ## Run the webhook locally against the demo policies (CPU plane unless JAX_PLATFORMS says otherwise; --backend tpu never falls back on its own)
	mkdir -p /tmp/cedar-demo/policies
	$(PYTHON) -c "import yaml,pathlib; \
	  docs=[d for p in ('demo/authorization-policy.yaml',) \
	        for d in yaml.safe_load_all(open(p)) if d]; \
	  pathlib.Path('/tmp/cedar-demo/policies/demo.cedar').write_text( \
	      chr(10).join(d['spec']['content'] for d in docs))"
	printf 'apiVersion: cedar.k8s.aws/v1alpha1\nkind: StoreConfig\nspec:\n  stores:\n    - type: "directory"\n      directoryStore:\n        path: "/tmp/cedar-demo/policies"\n' \
	    > /tmp/cedar-demo/config.yaml
	JAX_PLATFORMS=$${JAX_PLATFORMS-cpu} $(PYTHON) -m cedar_tpu.cli.webhook --config /tmp/cedar-demo/config.yaml \
	    --backend tpu --cert-dir /tmp/cedar-demo/certs

.PHONY: demo-policies
demo-policies: ## Render demo/*.yaml Policy content into mount/policies/ (canonical layout)
	$(PYTHON) -c "import yaml,pathlib; \
	  docs=[d for d in yaml.safe_load_all(open('demo/authorization-policy.yaml')) if d]; \
	  pathlib.Path('mount/policies/demo.cedar').write_text( \
	      chr(10).join(d['spec']['content'] for d in docs))"
	$(PYTHON) -m cedar_tpu.cli.policy_formatter mount/policies/demo.cedar

.PHONY: kind
kind: image demo-policies ## Create a kind cluster serving the webhook static pod
	kind create cluster --config kind.yaml
	kind load docker-image $(IMAGE)
	kubectl apply -k config/default
	@echo "webhook static pod manifest is mounted at"
	@echo "/etc/kubernetes/manifests/ (see kind.yaml extraMounts); policies"
	@echo "live in mount/policies/ (directory store, 1m refresh)"

.PHONY: deploy-admission-webhook
deploy-admission-webhook: ## Apply the ValidatingWebhookConfiguration with the serving CA injected
	@test -f $(CERT_DIR)/cedar-authorizer-server.crt || { \
	  echo "no serving cert at $(CERT_DIR)/cedar-authorizer-server.crt (start the" \
	       "webhook once to self-sign, or set CERT_DIR)"; exit 1; }
	sed "s/CA_BUNDLE/$$(base64 -w0 < $(CERT_DIR)/cedar-authorizer-server.crt)/" \
	    manifests/admission-webhook.yaml | kubectl apply -f -

##@ General

.PHONY: help
help: ## Show this help
	@awk 'BEGIN {FS = ":.*##"} /^[a-zA-Z_0-9-]+:.*?##/ \
	  { printf "  \033[36m%-22s\033[0m %s\n", $$1, $$2 } /^##@/ \
	  { printf "\n\033[1m%s\033[0m\n", substr($$0, 5) }' $(MAKEFILE_LIST)
