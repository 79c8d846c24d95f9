"""Beacon REST API: the standard eth2 node HTTP surface.

Equivalent of the reference's beacon REST API (reference: data/
beaconrestapi/src/main/java/tech/pegasys/teku/beaconrestapi/
JsonTypeDefinitionBeaconRestApi.java and handlers/v1/{node,beacon,
validator,config}/): node identity/health/syncing, chain queries
(genesis, headers, blocks, finality checkpoints, validators), pool
submission, duty queries, spec config, plus the Prometheus /metrics
exposition (infrastructure/metrics MetricsEndpoint analogue).
"""

import logging
from typing import Optional

from ..infra import tracing
from ..infra.metrics import GLOBAL_REGISTRY
from ..infra.restapi import HttpError, RestApi
from ..spec import helpers as H

_LOG = logging.getLogger(__name__)

VERSION = "teku-tpu/0.3.0"


def _hex(b: bytes) -> str:
    return "0x" + b.hex()


# schema-driven SSZ<->JSON (shared with the Web3Signer client)
from ..ssz.json import ssz_from_json as _ssz_from_json  # noqa: E402
from ..ssz.json import ssz_to_json as _ssz_to_json  # noqa: E402


class BeaconRestApi(RestApi):
    """Routes bound to one BeaconNode (and optionally its p2p net)."""

    def __init__(self, node, networked=None, host: str = "127.0.0.1",
                 port: int = 0, validator_api=None, database=None):
        super().__init__(host, port)
        self.node = node
        self.networked = networked
        self.validator_api = validator_api
        # archive database: serves historical blocks/states the hot
        # store has moved past (regenerating states from snapshots)
        self.database = database
        g = self.get
        p = self.post
        g("/eth/v1/node/health", self._health)
        g("/eth/v1/node/version", self._version)
        g("/eth/v1/node/identity", self._identity)
        g("/eth/v1/node/syncing", self._syncing)
        g("/eth/v1/node/peers", self._peers)
        g("/eth/v1/beacon/genesis", self._genesis)
        g("/eth/v1/beacon/headers/{block_id}", self._header)
        g("/eth/v2/beacon/blocks/{block_id}", self._block)
        g("/eth/v1/beacon/states/{state_id}/root", self._state_root)
        g("/eth/v1/beacon/states/{state_id}/finality_checkpoints",
          self._finality)
        g("/eth/v1/beacon/states/{state_id}/validators", self._validators)
        g("/eth/v1/config/spec", self._spec_config)
        g("/eth/v1/validator/duties/proposer/{epoch}", self._proposer_duties)
        p("/eth/v1/validator/duties/attester/{epoch}", self._attester_duties)
        p("/eth/v1/validator/duties/sync/{epoch}", self._sync_duties)
        p("/eth/v1/validator/liveness/{epoch}", self._liveness)
        g("/eth/v1/beacon/states/{state_id}/committees", self._committees)
        g("/eth/v1/beacon/states/{state_id}/sync_committees",
          self._state_sync_committees)
        g("/eth/v1/config/fork_schedule", self._fork_schedule)
        g("/eth/v1/beacon/rewards/blocks/{block_id}",
          self._block_rewards)
        p("/eth/v1/beacon/rewards/attestations/{epoch}",
          self._attestation_rewards)
        p("/eth/v1/beacon/rewards/sync_committee/{block_id}",
          self._sync_committee_rewards)
        p("/eth/v1/validator/beacon_committee_subscriptions",
          self._committee_subscriptions)
        p("/eth/v1/validator/sync_committee_subscriptions",
          self._sync_subscriptions)
        p("/eth/v1/validator/prepare_beacon_proposer",
          self._prepare_proposer)
        p("/eth/v1/validator/register_validator",
          self._register_validator)
        p("/eth/v1/beacon/pool/attestations", self._submit_attestations)
        p("/eth/v1/beacon/pool/voluntary_exits", self._submit_exit)
        p("/eth/v1/beacon/pool/sync_committees", self._submit_sync_messages)
        # op-pool family (reference data/beaconrestapi handlers/v1/
        # beacon: Get/PostAttesterSlashings, Get/PostProposerSlashings,
        # Get/PostBlsToExecutionChanges)
        g("/eth/v1/beacon/pool/voluntary_exits", self._get_pool_exits)
        g("/eth/v1/beacon/pool/attester_slashings",
          self._get_attester_slashings)
        p("/eth/v1/beacon/pool/attester_slashings",
          self._post_attester_slashing)
        g("/eth/v1/beacon/pool/proposer_slashings",
          self._get_proposer_slashings)
        p("/eth/v1/beacon/pool/proposer_slashings",
          self._post_proposer_slashing)
        g("/eth/v1/beacon/pool/bls_to_execution_changes",
          self._get_bls_changes)
        p("/eth/v1/beacon/pool/bls_to_execution_changes",
          self._post_bls_changes)
        # v2 pool family: electra-era versioned envelope (reference
        # handlers/v2/beacon/GetAttesterSlashingsV2.java etc.)
        g("/eth/v2/beacon/pool/attester_slashings",
          self._get_attester_slashings_v2)
        p("/eth/v2/beacon/pool/attester_slashings",
          self._post_attester_slashing)
        g("/eth/v2/beacon/pool/proposer_slashings",
          self._get_proposer_slashings_v2)
        p("/eth/v2/beacon/pool/proposer_slashings",
          self._post_proposer_slashing)
        g("/eth/v1/beacon/states/{state_id}/validator_balances",
          self._validator_balances)
        p("/eth/v1/beacon/states/{state_id}/validator_balances",
          self._validator_balances_post)
        g("/eth/v1/beacon/blocks/{block_id}/root", self._block_root)
        g("/eth/v1/beacon/blocks/{block_id}/attestations",
          self._block_attestations)
        g("/eth/v1/node/peer_count", self._peer_count)
        g("/eth/v1/beacon/states/{state_id}/expected_withdrawals",
          self._expected_withdrawals)
        g("/eth/v1/beacon/blob_sidecars/{block_id}", self._blob_sidecars)
        # the remote-VC surface (reference: handlers/v1/validator/* and
        # the debug state endpoint checkpoint sync reads)
        g("/eth/v2/debug/beacon/states/{state_id}", self._state_ssz)
        g("/eth/v1/validator/attestation_data", self._attestation_data)
        g("/eth/v1/validator/aggregate_attestation",
          self._aggregate_attestation)
        g("/eth/v3/validator/blocks/{slot}", self._produce_block)
        p("/eth/v2/beacon/blocks", self._publish_block_ssz)
        p("/eth/v1/validator/aggregate_and_proofs",
          self._submit_aggregate_ssz)
        g("/eth/v1/validator/sync_committee_contribution",
          self._sync_contribution)
        p("/eth/v1/validator/contribution_and_proofs",
          self._submit_contribution_ssz)
        g("/eth/v1/events", self._events)
        g("/eth/v1/beacon/light_client/bootstrap/{block_id}",
          self._lc_bootstrap)
        g("/eth/v1/beacon/light_client/finality_update",
          self._lc_finality_update)
        g("/eth/v1/beacon/light_client/updates", self._lc_updates)
        g("/eth/v1/node/peers/{peer_id}", self._peer_by_id)
        g("/eth/v1/debug/fork_choice", self._debug_fork_choice)
        # slow-trace dump (per-stage breakdowns of the slowest
        # verifies) — teku-namespaced like the reference's /teku/v1
        # operator endpoints
        g("/teku/v1/admin/traces", self._admin_traces)
        g("/teku/v1/admin/readiness", self._admin_readiness)
        g("/teku/v1/admin/flight_recorder", self._admin_flight_recorder)
        g("/teku/v1/admin/capacity", self._admin_capacity)
        g("/teku/v1/admin/dispatches", self._admin_dispatches)
        g("/teku/v1/admin/admission", self._admin_admission)
        g("/teku/v1/admin/profile", self._admin_profile)
        g("/teku/v1/admin/timeline", self._admin_timeline)
        g("/metrics", self._metrics)

    # -- resolution helpers -------------------------------------------
    def _resolve_block_root(self, block_id: str) -> bytes:
        chain = self.node.chain
        if block_id == "head":
            return chain.head_root
        if block_id == "finalized":
            return chain.finalized_checkpoint.root
        if block_id == "justified":
            return chain.justified_checkpoint.root
        if block_id.startswith("0x"):
            try:
                root = bytes.fromhex(block_id[2:])
            except ValueError:
                raise HttpError(400, f"invalid root {block_id!r}")
            if len(root) != 32:
                raise HttpError(400, "root must be 32 bytes")
            if chain.contains_block(root):
                return root
            if self.database is not None \
                    and self.database.has_block(root):
                return root
            raise HttpError(404, "block not found")
        try:
            slot = int(block_id)
        except ValueError:
            raise HttpError(400, f"invalid block id {block_id!r}")
        if slot < 0:
            raise HttpError(400, "slot must be non-negative")
        root = self.node.store.proto.ancestor_at_slot(chain.head_root, slot)
        if root is None or self.node.store.blocks[root].slot != slot:
            # historical: the finalized slot index in the archive
            if self.database is not None:
                db_root = self.database.canonical_root_at_slot(slot)
                if db_root is not None:
                    return db_root
            raise HttpError(404, "no canonical block at slot")
        return root

    async def _state_by_root_async(self, root: bytes):
        """Hot store, else archive regeneration in an executor (the
        replay can be ~snapshot_interval state transitions — it must
        not stall duty queries on the event loop); None if unknown."""
        state = self.node.chain.get_state(root)
        if state is None and self.database is not None:
            import asyncio
            state = await asyncio.get_running_loop().run_in_executor(
                None, self.database.get_or_regenerate_state, root)
        return state

    async def _resolve_state_async(self, state_id: str):
        root = self._resolve_block_root(
            "head" if state_id == "head" else state_id)
        state = await self._state_by_root_async(root)
        if state is None:
            raise HttpError(404, "state not available")
        return state

    # -- node ----------------------------------------------------------
    def _is_syncing(self) -> bool:
        return bool(self.networked and self.networked.sync.syncing)

    async def _health(self, query=None):
        """Spec-correct node health (reference handlers/v1/node/
        GetHealth.java): 200 ready, 206 syncing or DEGRADED (serving,
        but impaired), 503 DOWN — driven by the live HealthRegistry,
        not a stub.  The optional ``syncing_status`` query param
        substitutes the 206 (per the Beacon API spec: any valid HTTP
        code; invalid values are a 400)."""
        from ..infra.health import HealthStatus
        health = getattr(self.node, "health", None)
        status = health.evaluate() if health is not None \
            else HealthStatus.UP
        syncing_code = 206
        if query and "syncing_status" in query:
            try:
                syncing_code = int(query["syncing_status"])
            except ValueError:
                raise HttpError(400, "syncing_status must be an "
                                     "integer status code")
            if not 100 <= syncing_code < 600:
                raise HttpError(400, "syncing_status out of range "
                                     "(100-599)")
        if status is HealthStatus.DOWN:
            return {}, None, 503
        # the override substitutes ONLY the syncing response (its spec
        # contract) — a ?syncing_status=200 probe keeping syncing nodes
        # in rotation must not also mask genuine degradation
        if self._is_syncing():
            return {}, None, syncing_code
        if status is HealthStatus.DEGRADED:
            return {}, None, 206
        return {}, None, 200

    async def _admin_readiness(self):
        """Detailed operator/autoscaler readiness: every health check's
        verdict + detail, the SLO burn rates, and sync state — the
        'WHICH subsystem is hurting' companion to /eth/v1/node/health's
        one status code."""
        health = getattr(self.node, "health", None)
        slo = getattr(self.node, "slo", None)
        if health is None:
            raise HttpError(503, "health registry not wired")
        health.evaluate()
        out = health.snapshot()
        out["syncing"] = self._is_syncing()
        if slo is not None:
            out["slo"] = slo.snapshot()
        sup = getattr(self.node, "supervisor", None)
        if sup is not None:
            out["backend"] = sup.snapshot()
        # brownout state rides the readiness body: an autoscaler or
        # load balancer deciding where to send traffic needs "this
        # node is deliberately shedding OPTIMISTIC/GOSSIP" next to
        # the per-check verdicts, not on a separate endpoint
        admission = getattr(self.node, "admission", None)
        if admission is not None:
            snap = admission.snapshot()
            out["admission"] = {"brownout": snap["brownout"],
                                "plan": snap["plan"],
                                "inputs": snap["inputs"]}
        return out

    async def _admin_flight_recorder(self, query=None):
        """The flight-recorder ring as JSON, oldest first: backend
        state transitions, breaker trips, SLO breaches, queue sheds,
        health flips — each with its originating trace id.  `?last=N`
        tails, `?clear=1` empties after the read, `?dump=1` also
        writes the JSONL file an incident report wants."""
        recorder = getattr(self.node, "flight_recorder", None)
        if recorder is None:
            raise HttpError(503, "flight recorder not wired")
        last = None
        if query and query.get("last"):
            try:
                last = max(1, int(query["last"]))
            except ValueError:
                raise HttpError(400, "last must be an integer")
        out = {"data": recorder.snapshot(last=last)}
        if query and query.get("dump") in ("1", "true"):
            out["dumped_to"] = recorder.dump("operator request")
        if query and query.get("clear") in ("1", "true"):
            recorder.clear()
        return out

    async def _admin_capacity(self):
        """The node's self-measurement (infra/capacity.py): per-shape
        device-latency model, arrival rates per source, queue-depth
        series, shed rate, true device occupancy, and the derived
        sustainable-sigs/sec + utilization/headroom signals the
        adaptive batcher (ROADMAP 3) will consume.  refresh() also
        fires the edge-triggered headroom-exhausted flight-recorder
        event, so polling this endpoint keeps the evidence current
        even between node health ticks."""
        from ..infra import capacity
        return {"data": capacity.refresh()}

    async def _admin_dispatches(self, query=None):
        """The dispatch decision ledger (infra/dispatchledger.py):
        bounded structured per-dispatch records — batch plan mode and
        brownout level, real vs padded lanes and unique counts (waste
        split by stage bucket), H(m) cache hits/misses, mesh shard
        plan + makespan ratio, compile outcome with duration, device
        sync/busy spans, verdict — each stamped with its originating
        trace ids.  ``?last=N`` tails,
        ``?trace_id=X`` filters to the record serving that trace (the
        slow-trace ring's join key), ``?slow=1`` filters to records
        linked to the current slow-trace ring."""
        from ..infra import dispatchledger
        last = None
        if query and query.get("last"):
            try:
                last = max(1, int(query["last"]))
            except ValueError:
                raise HttpError(400, "last must be an integer")
        trace_id = (query or {}).get("trace_id") or None
        slow = (query or {}).get("slow") in ("1", "true")
        ledger = dispatchledger.LEDGER
        records = ledger.snapshot(last=last, trace_id=trace_id,
                                  slow=slow)
        return {"data": {
            "records": records,
            "summary": dispatchledger.summarize(records),
            "capacity": ledger.capacity,
            "recorded_total": ledger.recorded_total}}

    async def _admin_timeline(self, query=None):
        """The unified causal timeline (infra/timeline.py): every
        observability ring joined on the shared clock spine.  With
        ``?trace_id=X`` returns the full joined view for that trace —
        gap-free span tree, the ledger record that served it, its
        flight-recorder entries and timeline ring events — as a
        schema-versioned envelope.  Without a trace id returns the
        anchor, the slow-trace ring and the timeline ring (the raw
        material ``cli timeline`` turns into a Perfetto trace)."""
        from ..infra import dispatchledger, schema, timeline
        trace_id = (query or {}).get("trace_id") or None
        recorder = getattr(self.node, "flight_recorder", None)
        flight = recorder.snapshot() if recorder is not None else []
        if trace_id:
            return timeline.join(
                trace_id,
                tracing.slow_traces(),
                dispatchledger.LEDGER.snapshot(trace_id=trace_id),
                [e for e in flight
                 if e.get("trace_id") == trace_id],
                timeline.RING.snapshot(trace_id=trace_id))
        last = None
        if query and query.get("last"):
            try:
                last = max(1, int(query["last"]))
            except ValueError:
                raise HttpError(400, "last must be an integer")
        from ..infra import clock
        return schema.envelope("timeline", {
            "anchor": clock.anchor_dict(),
            "enabled": timeline.enabled(),
            "traces": tracing.slow_traces(),
            "ring": timeline.RING.snapshot(last=last),
        })

    async def _admin_admission(self):
        """The overload controller's state (services/admission.py):
        the current BatchPlan (adaptive pow-2 batch size + flush
        deadline and the modeled device time behind them), the
        brownout state machine (level, shed classes, hysteresis
        counters, edge counts), the driving inputs (utilization, p50
        burn rate, queue depth), the full knob config, and the
        per-class queue depths/ages from the signature service."""
        ctl = getattr(self.node, "admission", None)
        if ctl is None:
            raise HttpError(503, "admission controller not wired "
                                 "(overload control off)")
        out = {"controller": ctl.snapshot()}
        svc = getattr(self.node, "sig_service", None)
        if svc is not None:
            out["queues"] = svc.queue_snapshot()
        return {"data": out}

    async def _admin_profile(self, query=None):
        """On-demand jax.profiler capture (infra/profiling.py):
        ``?start=1`` begins a capture (optional ``&duration_s=N`` arms
        the auto-stop the health tick enforces), ``?stop=1`` ends it
        and names the trace directory, no params = status (active
        capture, last capture, cooldown/trigger config).  Start/stop
        are also recorded to the flight recorder with the originating
        trace id."""
        from ..infra import profiling
        ctl = profiling.CONTROLLER
        if query and query.get("start") in ("1", "true"):
            duration = None
            if query.get("duration_s"):
                try:
                    duration = max(0.1, float(query["duration_s"]))
                except ValueError:
                    raise HttpError(400, "duration_s must be a number")
            return {"data": ctl.start(trigger="manual",
                                      duration_s=duration)}
        if query and query.get("stop") in ("1", "true"):
            return {"data": ctl.stop()}
        return {"data": ctl.status()}

    async def _version(self):
        return {"data": {"version": VERSION}}

    async def _identity(self):
        node_id = (self.networked.net.node_id.hex()
                   if self.networked else "00" * 32)
        attnets = bytearray(8)
        manager = getattr(self.networked, "subnets", None) \
            if self.networked else None
        if manager is not None:
            for subnet in manager.active_subnets():
                attnets[subnet // 8] |= 1 << (subnet % 8)
        enr = getattr(self.networked, "enr", None) \
            if self.networked else None
        return {"data": {"peer_id": node_id,
                         "enr": enr.to_text() if enr else "",
                         "p2p_addresses": [], "metadata": {
                             "seq_number": "0",
                             "attnets": "0x" + bytes(attnets).hex()}}}

    async def _syncing(self):
        syncing = bool(self.networked and self.networked.sync.syncing)
        head = self.node.chain.head_slot()
        current = self.node.chain.current_slot()
        return {"data": {"head_slot": str(head),
                         "sync_distance": str(max(0, current - head)),
                         "is_syncing": syncing,
                         "is_optimistic": False, "el_offline": False}}

    @staticmethod
    def _peer_json(peer) -> dict:
        return {"peer_id": peer.node_id.hex(),
                "state": "connected" if peer.connected
                else "disconnected",
                "direction": "outbound" if peer.outbound
                else "inbound",
                "last_seen_p2p_address": ""}

    async def _peers(self):
        peers = []
        if self.networked:
            for peer in self.networked.net.peers:
                peers.append(self._peer_json(peer))
        return {"data": peers,
                "meta": {"count": len(peers)}}

    # -- beacon --------------------------------------------------------
    async def _genesis(self):
        # every state carries the same genesis fields
        state = self.node.chain.head_state()
        return {"data": {
            "genesis_time": str(state.genesis_time),
            "genesis_validators_root": _hex(state.genesis_validators_root),
            "genesis_fork_version": _hex(
                self.node.spec.config.GENESIS_FORK_VERSION)}}

    def _block_by_root(self, root: bytes):
        """Hot store first, then the archive (the resolver may return
        roots only the database holds)."""
        block = self.node.store.blocks.get(root)
        if block is None and self.database is not None:
            signed = self.database.get_block(root)
            block = signed.message if signed is not None else None
        if block is None:
            raise HttpError(404, "block not found")
        return block

    async def _header(self, block_id: str):
        root = self._resolve_block_root(block_id)
        block = self._block_by_root(root)
        return {"data": {
            "root": _hex(root),
            "canonical": True,
            "header": {"message": {
                "slot": str(block.slot),
                "proposer_index": str(block.proposer_index),
                "parent_root": _hex(block.parent_root),
                "state_root": _hex(block.state_root),
                "body_root": _hex(block.body.htr())}}},
            "execution_optimistic": False, "finalized": False}

    async def _blob_sidecars(self, block_id: str):
        """Deneb blob sidecars for one block (reference: handlers/v1/
        beacon/GetBlobSidecars.java), served from the tracking pool."""
        root = self._resolve_block_root(block_id)
        pool = getattr(self.node, "blob_pool", None)
        sidecars = pool.wire_sidecars_for(root) if pool is not None else []
        out = []
        for sc in sidecars:
            hdr = sc.signed_block_header.message
            out.append({
                "index": str(sc.index),
                "blob": _hex(bytes(sc.blob)),
                "kzg_commitment": _hex(sc.kzg_commitment),
                "kzg_proof": _hex(sc.kzg_proof),
                "signed_block_header": {
                    "message": {
                        "slot": str(hdr.slot),
                        "proposer_index": str(hdr.proposer_index),
                        "parent_root": _hex(hdr.parent_root),
                        "state_root": _hex(hdr.state_root),
                        "body_root": _hex(hdr.body_root),
                    },
                    "signature": _hex(sc.signed_block_header.signature),
                },
                "kzg_commitment_inclusion_proof": [
                    _hex(h) for h in sc.kzg_commitment_inclusion_proof],
            })
        return {"data": out}

    async def _block(self, block_id: str, query=None, headers=None):
        root = self._resolve_block_root(block_id)
        signed = self.node.store.signed_blocks.get(root)
        if signed is None and self.database is not None:
            signed = self.database.get_block(root)
        if signed is None:
            raise HttpError(404, "signed block not retained")
        wants_ssz = ("application/octet-stream"
                     in (headers or {}).get("accept", "")
                     or (query or {}).get("format") == "ssz")
        if wants_ssz:
            # octet-stream variant per the standard Accept negotiation
            # — checkpoint sync's block fetch
            return type(signed).serialize(signed), \
                "application/octet-stream"
        block = signed.message
        version = self.node.spec.milestone_at_slot(block.slot).name.lower()
        return {"version": version, "data": {
            "message": {
                "slot": str(block.slot),
                "proposer_index": str(block.proposer_index),
                "parent_root": _hex(block.parent_root),
                "state_root": _hex(block.state_root),
                "body": {
                    "randao_reveal": _hex(block.body.randao_reveal),
                    "graffiti": _hex(block.body.graffiti),
                    "attestations_count": len(block.body.attestations)},
            },
            "signature": _hex(signed.signature)}}

    async def _state_ssz(self, state_id: str):
        """Full state as SSZ (reference GetState debug handler) — the
        fetch behind checkpoint sync and the remote VC's duty states."""
        state = await self._resolve_state_async(state_id)
        return type(state).serialize(state), "application/octet-stream"

    async def _attestation_data(self, query=None):
        if self.validator_api is None:
            raise HttpError(503, "validator api not wired")
        try:
            slot = int((query or {})["slot"])
            ci = int((query or {})["committee_index"])
        except (KeyError, ValueError):
            raise HttpError(400, "slot and committee_index required")
        data = self.validator_api.get_attestation_data(slot, ci)
        return {"data": {
            "slot": str(data.slot), "index": str(data.index),
            "beacon_block_root": _hex(data.beacon_block_root),
            "source": {"epoch": str(data.source.epoch),
                       "root": _hex(data.source.root)},
            "target": {"epoch": str(data.target.epoch),
                       "root": _hex(data.target.root)}}}

    async def _aggregate_attestation(self, query=None):
        try:
            root = bytes.fromhex(
                (query or {})["attestation_data_root"][2:])
        except (KeyError, ValueError):
            raise HttpError(400, "attestation_data_root required")
        ci = None
        if query and "committee_index" in query:
            try:
                ci = int(query["committee_index"])
            except ValueError:
                raise HttpError(400, "invalid committee_index")
        aggregate = self.node.pool.get_aggregate_by_root(root, ci)
        if aggregate is None:
            raise HttpError(404, "no aggregate for this data")
        return type(aggregate).serialize(aggregate), \
            "application/octet-stream"

    async def _produce_block(self, slot: str, query=None):
        """Unsigned block production for the remote VC (reference
        produceBlockV3) — SSZ response; the VC signs and POSTs back."""
        if self.validator_api is None:
            raise HttpError(503, "validator api not wired")
        try:
            reveal = bytes.fromhex((query or {})["randao_reveal"][2:])
        except (KeyError, ValueError):
            raise HttpError(400, "randao_reveal required")
        graffiti = bytes(32)
        if query and "graffiti" in query:
            graffiti = bytes.fromhex(query["graffiti"][2:]).ljust(32,
                                                                  b"\x00")
        try:
            block, _pre = await self.validator_api.produce_unsigned_block(
                int(slot), reveal, graffiti)
        except Exception as exc:
            raise HttpError(500, f"block production failed: {exc}")
        return type(block).serialize(block), "application/octet-stream"

    async def _publish_block_ssz(self, raw_body=None):
        if not raw_body:
            raise HttpError(400, "SSZ SignedBeaconBlock body required")
        from ..spec.codec import deserialize_signed_block
        try:
            signed = deserialize_signed_block(self.node.spec.config,
                                              raw_body)
        except Exception as exc:
            raise HttpError(400, f"malformed block: {exc}")
        if self.validator_api is not None:
            await self.validator_api.publish_signed_block(signed)
        else:
            self.node.block_manager.import_block(signed)
        return {}

    async def _sync_contribution(self, query=None):
        """Produce a sync-committee contribution (reference
        GetSyncCommitteeContribution) — SSZ response."""
        if self.validator_api is None:
            raise HttpError(503, "validator api not wired")
        try:
            slot = int((query or {})["slot"])
            sub = int((query or {})["subcommittee_index"])
            root = bytes.fromhex(
                (query or {})["beacon_block_root"][2:])
        except (KeyError, ValueError):
            raise HttpError(
                400, "slot, subcommittee_index, beacon_block_root "
                     "required")
        build = getattr(self.validator_api, "build_sync_contribution",
                        None)
        if build is None:
            raise HttpError(503, "contributions not supported")
        contribution = build(slot, root, sub)
        if contribution is None:
            raise HttpError(404, "no messages pooled for this root")
        return type(contribution).serialize(contribution), \
            "application/octet-stream"

    async def _submit_contribution_ssz(self, raw_body=None):
        if not raw_body:
            raise HttpError(400, "SSZ SignedContributionAndProof "
                                 "required")
        signed = self._decode_versioned("SignedContributionAndProof",
                                        raw_body)
        publish = getattr(self.validator_api,
                          "publish_contribution_and_proof", None)
        if publish is None:
            raise HttpError(503, "contributions not supported")
        await publish(signed)
        return {}

    async def _submit_aggregate_ssz(self, raw_body=None):
        if not raw_body:
            raise HttpError(400, "SSZ SignedAggregateAndProof required")
        signed = self._decode_versioned("SignedAggregateAndProof",
                                        raw_body)
        if self.validator_api is None:
            raise HttpError(503, "validator api not wired")
        await self.validator_api.publish_aggregate_and_proof(signed)
        return {}

    async def _state_root(self, state_id: str):
        state = await self._resolve_state_async(state_id)
        return {"data": {"root": _hex(state.htr())}}

    async def _finality(self, state_id: str):
        state = await self._resolve_state_async(state_id)
        def cp(c):
            return {"epoch": str(c.epoch), "root": _hex(c.root)}
        return {"data": {
            "previous_justified": cp(state.previous_justified_checkpoint),
            "current_justified": cp(state.current_justified_checkpoint),
            "finalized": cp(state.finalized_checkpoint)}}

    async def _validators(self, state_id: str, query=None):
        state = await self._resolve_state_async(state_id)
        cfg = self.node.spec.config
        epoch = H.get_current_epoch(cfg, state)
        from ..spec.config import FAR_FUTURE_EPOCH
        out = []
        for i, v in enumerate(state.validators):
            if H.is_active_validator(v, epoch):
                status = ("active_slashed" if v.slashed
                          else "active_exiting"
                          if v.exit_epoch != FAR_FUTURE_EPOCH
                          else "active_ongoing")
            elif epoch >= v.exit_epoch:
                status = ("withdrawal_possible"
                          if epoch >= v.withdrawable_epoch
                          else "exited_slashed" if v.slashed
                          else "exited_unslashed")
            else:
                status = ("pending_queued"
                          if v.activation_eligibility_epoch
                          != FAR_FUTURE_EPOCH else "pending_initialized")
            out.append({"index": str(i),
                        "balance": str(state.balances[i]),
                        "status": status,
                        "validator": {
                            "pubkey": _hex(v.pubkey),
                            "effective_balance": str(v.effective_balance),
                            "slashed": v.slashed,
                            "activation_epoch": str(v.activation_epoch),
                            "exit_epoch": str(v.exit_epoch)}})
        return {"data": out}

    async def _spec_config(self):
        cfg = self.node.spec.config
        out = {}
        for name in cfg.__dataclass_fields__:
            v = getattr(cfg, name)
            out[name] = _hex(v) if isinstance(v, bytes) else str(v)
        return {"data": out}

    # -- validator -----------------------------------------------------
    async def _proposer_duties(self, epoch: str):
        if self.validator_api is None:
            raise HttpError(503, "validator api not wired")
        duties = self.validator_api.get_proposer_duties(int(epoch))
        state = self.node.chain.head_state()
        return {"data": [
            {"pubkey": _hex(
                state.validators[d.validator_index].pubkey),
             "validator_index": str(d.validator_index),
             "slot": str(d.slot)} for d in duties]}

    async def _attester_duties(self, epoch: str, body=None):
        if self.validator_api is None:
            raise HttpError(503, "validator api not wired")
        indices = [int(i) for i in (body or [])]
        duties = self.validator_api.get_attester_duties(int(epoch), indices)
        state = self.node.chain.head_state()
        return {"data": [
            {"pubkey": _hex(state.validators[d.validator_index].pubkey),
             "validator_index": str(d.validator_index),
             "committee_index": str(d.committee_index),
             "committee_length": str(d.committee_size),
             "committees_at_slot": str(d.committees_at_slot),
             "validator_committee_index": str(d.committee_position),
             "slot": str(d.slot)} for d in duties]}

    async def _sync_duties(self, epoch: str, body=None):
        """Sync-committee duties (reference handlers/v1/validator/
        PostSyncDuties.java:43) — what lets the remote VC run sync
        duties without downloading states."""
        if self.validator_api is None:
            raise HttpError(503, "validator api not wired")
        indices = [int(i) for i in (body or [])]
        duties = self.validator_api.get_sync_duties(int(epoch), indices)
        return {"execution_optimistic": False, "data": [
            {"pubkey": _hex(d.pubkey),
             "validator_index": str(d.validator_index),
             "validator_sync_committee_indices":
                 [str(p) for p in d.positions]}
            for d in duties]}

    async def _liveness(self, epoch: str, body=None):
        """Per-validator liveness from the epoch's participation flags
        (reference handlers/v1/validator/PostValidatorLiveness.java —
        there from a seen-attestation cache; here the participation
        registry IS that record for current/previous epoch)."""
        epoch = int(epoch)
        state = self.node.chain.head_state()
        cfg = self.node.spec.config
        current = H.get_current_epoch(cfg, state)
        if epoch == current:
            participation = getattr(state, "current_epoch_participation",
                                    None)
        elif epoch == current - 1:
            participation = getattr(state, "previous_epoch_participation",
                                    None)
        else:
            raise HttpError(400, "liveness only for current/previous "
                                 "epoch")
        if participation is None:
            raise HttpError(501, "pre-altair state has no participation "
                                 "registry")
        out = []
        for i in (body or []):
            vi = int(i)
            live = (vi < len(participation)
                    and participation[vi] != 0)
            out.append({"index": str(vi), "is_live": live})
        return {"data": out}

    async def _committees(self, state_id: str, query=None):
        """Beacon committees (reference handlers/v1/beacon/
        GetStateCommittees.java): all committees for an epoch, or
        filtered by slot/index."""
        query = query or {}
        state = await self._resolve_state_async(state_id)
        cfg = self.node.spec.config
        epoch = (int(query["epoch"]) if "epoch" in query
                 else H.get_current_epoch(cfg, state))
        want_slot = int(query["slot"]) if "slot" in query else None
        want_index = int(query["index"]) if "index" in query else None
        committees = H.get_committee_count_per_slot(cfg, state, epoch)
        first = H.compute_start_slot_at_epoch(cfg, epoch)
        out = []
        for slot in range(first, first + cfg.SLOTS_PER_EPOCH):
            if want_slot is not None and slot != want_slot:
                continue
            for ci in range(committees):
                if want_index is not None and ci != want_index:
                    continue
                try:
                    members = H.get_beacon_committee(cfg, state, slot, ci)
                except Exception:
                    raise HttpError(400, "epoch out of shuffling range")
                out.append({"index": str(ci), "slot": str(slot),
                            "validators": [str(v) for v in members]})
        return {"execution_optimistic": False, "data": out}

    async def _state_sync_committees(self, state_id: str, query=None):
        """Current sync committee of a state as validator indices
        (reference handlers/v1/beacon/GetStateSyncCommittees.java)."""
        state = await self._resolve_state_async(state_id)
        if not hasattr(state, "current_sync_committee"):
            raise HttpError(400, "pre-altair state")
        by_pubkey = {v.pubkey: i for i, v in enumerate(state.validators)}
        indices = [by_pubkey.get(pk)
                   for pk in state.current_sync_committee.pubkeys]
        if any(i is None for i in indices):
            raise HttpError(500, "committee pubkey not in registry")
        from ..spec.altair.helpers import sync_subcommittee_size
        sub = sync_subcommittee_size(self.node.spec.config)
        return {"execution_optimistic": False, "data": {
            "validators": [str(i) for i in indices],
            "validator_aggregates": [
                [str(i) for i in indices[off:off + sub]]
                for off in range(0, len(indices), sub)]}}

    async def _fork_schedule(self):
        """All scheduled forks (reference handlers/v1/config/
        GetForkSchedule.java) — lets a remote VC build signing domains
        for any epoch without a state."""
        from ..spec.milestones import build_fork_schedule
        schedule = build_fork_schedule(self.node.spec.config)
        out = []
        for i, v in enumerate(schedule.versions):
            prev = schedule.versions[i - 1] if i > 0 else v
            out.append({
                "previous_version": _hex(prev.fork_version),
                "current_version": _hex(v.fork_version),
                "epoch": str(v.fork_epoch)})
        return {"data": out}

    async def _pre_post_states(self, root: bytes):
        """(pre_state_at_block_slot, post_state, block) for a block —
        the reward endpoints' shared setup."""
        from ..spec.transition import process_slots
        block = self._block_by_root(root)
        post = await self._state_by_root_async(root)
        parent_state = await self._state_by_root_async(
            block.parent_root)
        if post is None or parent_state is None:
            raise HttpError(404, "states not available for rewards")
        pre = parent_state
        if pre.slot < block.slot:
            pre = process_slots(self.node.spec.config, pre, block.slot)
        return pre, post, block

    def _validator_indices(self, state, body) -> list:
        """The beacon-API 'validator index or pubkey' body shape."""
        by_pubkey = None
        out = []
        for item in (body or []):
            item = str(item)
            if item.startswith("0x"):
                if by_pubkey is None:
                    by_pubkey = {v.pubkey: i
                                 for i, v in enumerate(state.validators)}
                try:
                    index = by_pubkey.get(bytes.fromhex(item[2:]))
                except ValueError:
                    raise HttpError(400, f"bad pubkey {item!r}")
                if index is None:
                    raise HttpError(404, f"unknown validator {item!r}")
                out.append(index)
            else:
                try:
                    out.append(int(item))
                except ValueError:
                    raise HttpError(400, f"bad validator id {item!r}")
        return out

    async def _block_rewards(self, block_id: str):
        """reference handlers/v1/rewards/GetBlockRewards.java."""
        from . import rewards as R
        root = self._resolve_block_root(block_id)
        pre, post, block = await self._pre_post_states(root)
        out = R.block_rewards(self.node.spec.config, pre, post, block)
        return {"execution_optimistic": False, "finalized": False,
                "data": {k: str(v) for k, v in out.items()}}

    async def _attestation_rewards(self, epoch: str, body=None):
        """reference handlers/v1/rewards/PostAttestationRewards.java —
        rewards for `epoch` read from a state one epoch later (whose
        previous-epoch participation covers it)."""
        from . import rewards as R
        cfg = self.node.spec.config
        epoch = int(epoch)
        head_state = self.node.chain.head_state()
        current = H.get_current_epoch(cfg, head_state)
        if epoch + 2 > current:
            # attestations for `epoch` are includable through ALL of
            # epoch+1 — rewards only settle once epoch+1 closes
            raise HttpError(400, "rewards settle after epoch+1 closes")
        # the LAST canonical block of epoch+1: its post-state holds the
        # final participation for `epoch` (rotated away at the next
        # boundary)
        start = H.compute_start_slot_at_epoch(cfg, epoch + 1)
        state = None
        for slot in range(start + cfg.SLOTS_PER_EPOCH - 1, start - 1,
                          -1):
            try:
                root = self._resolve_block_root(str(slot))
            except HttpError:
                continue
            state = await self._state_by_root_async(root)
            break
        if state is None:
            raise HttpError(404, "no state covering that epoch")
        indices = self._validator_indices(state, body) or None
        out = R.attestation_rewards(cfg, state, indices)
        return {"execution_optimistic": False, "finalized": False,
                "data": {
                    "ideal_rewards": [
                        {k: str(v) for k, v in row.items()}
                        for row in out["ideal_rewards"]],
                    "total_rewards": [
                        {k: str(v) for k, v in row.items()}
                        for row in out["total_rewards"]]}}

    async def _sync_committee_rewards(self, block_id: str, body=None):
        """reference handlers/v1/rewards/PostSyncCommitteeRewards."""
        from . import rewards as R
        root = self._resolve_block_root(block_id)
        pre, post, block = await self._pre_post_states(root)
        if not hasattr(block.body, "sync_aggregate") \
                or not hasattr(pre, "current_sync_committee"):
            raise HttpError(400, "pre-altair block has no sync rewards")
        _, _, deltas = R.sync_aggregate_rewards(
            self.node.spec.config, pre, block.body.sync_aggregate)
        wanted = set(self._validator_indices(pre, body)) or None
        return {"execution_optimistic": False, "finalized": False,
                "data": [
                    {"validator_index": str(i), "reward": str(d)}
                    for i, d in deltas
                    if wanted is None or i in wanted]}

    async def _committee_subscriptions(self, body=None):
        """reference handlers/v1/validator/PostSubscribeToBeaconCommittee
        Subnet.java: duty-driven subnet subscriptions from the VC.
        This node carries every attestation subnet (devnet-correct);
        the manager tracks the duty windows for expiry and for the
        attnets advertised by /eth/v1/node/identity.  Validation runs
        over the WHOLE body before any state changes."""
        if body is not None and not isinstance(body, list):
            raise HttpError(400, "body must be a list")
        from ..node.node import compute_subnet_for_attestation
        cfg = self.node.spec.config
        manager = getattr(self.networked, "subnets", None) \
            if self.networked else None
        parsed = []
        for sub in (body or []):
            try:
                parsed.append((int(sub["slot"]),
                               int(sub["committee_index"]),
                               int(sub["committees_at_slot"])))
            except (KeyError, ValueError, TypeError):
                raise HttpError(400, "malformed subscription")
        for slot, committee_index, committees in parsed:
            if manager is not None:
                subnet = compute_subnet_for_attestation(
                    cfg, committees, slot, committee_index)
                manager.subscribe_for_duty(subnet, slot + 1)
        return {"data": {"accepted": str(len(parsed))}}

    async def _sync_subscriptions(self, body=None):
        """reference PostSyncCommitteeSubscriptions — sync-committee
        topics are node-global in this stack, so acceptance is the
        whole contract."""
        if body is not None and not isinstance(body, list):
            raise HttpError(400, "body must be a list")
        for sub in (body or []):
            if not isinstance(sub, dict) or "validator_index" not in sub:
                raise HttpError(400, "malformed subscription")
        return {}

    async def _prepare_proposer(self, body=None):
        """reference PostPrepareBeaconProposer: fee recipients per
        proposer, consumed by block production (the devnet payload
        builder stamps them into execution_payload.fee_recipient)."""
        if body is not None and not isinstance(body, list):
            raise HttpError(400, "body must be a list")
        parsed = []
        for item in (body or []):
            try:
                index = int(item["validator_index"])
                recipient = bytes.fromhex(
                    item["fee_recipient"].removeprefix("0x"))
                if len(recipient) != 20:
                    raise ValueError("fee recipient must be 20 bytes")
            except (KeyError, ValueError, TypeError, AttributeError):
                raise HttpError(400, "malformed preparation")
            parsed.append((index, recipient))
        # all-or-nothing: nothing commits if any item was malformed
        prepared = getattr(self.node, "proposer_preparations", None)
        if prepared is None:
            prepared = {}
            self.node.proposer_preparations = prepared
        prepared.update(parsed)
        return {}

    async def _register_validator(self, body=None):
        """reference PostRegisterValidator: signed builder
        registrations, verified and forwarded to the builder when one
        is wired (otherwise retained for when it is)."""
        from ..builderapi import (SignedValidatorRegistration,
                                  ValidatorRegistration,
                                  verify_registration)
        if body is not None and not isinstance(body, list):
            raise HttpError(400, "body must be a list")
        cfg = self.node.spec.config
        registrations = []
        for item in (body or []):
            try:
                msg = item["message"]
                signed = SignedValidatorRegistration(
                    message=ValidatorRegistration(
                        fee_recipient=bytes.fromhex(
                            msg["fee_recipient"].removeprefix("0x")),
                        gas_limit=int(msg["gas_limit"]),
                        timestamp=int(msg["timestamp"]),
                        pubkey=bytes.fromhex(
                            msg["pubkey"].removeprefix("0x"))),
                    signature=bytes.fromhex(
                        item["signature"].removeprefix("0x")))
            except (KeyError, ValueError, TypeError,
                    AttributeError) as exc:
                raise HttpError(400, f"malformed registration: {exc}")
            registrations.append(signed)
        # signature checks off the event loop (a VC registers its
        # whole keyset at once; pairings would stall every endpoint)
        import asyncio

        def _verify_all():
            for signed in registrations:
                try:
                    if not verify_registration(cfg, signed):
                        return False
                except Exception:
                    return False       # SSZ length/range errors = 400
            return True
        if registrations and not await asyncio.get_running_loop() \
                .run_in_executor(None, _verify_all):
            raise HttpError(400, "bad registration signature")
        store = getattr(self.node, "validator_registrations", None)
        if store is None:
            store = {}
            self.node.validator_registrations = store
        for signed in registrations:
            store[signed.message.pubkey] = signed
        # forwarded when a builder relay is wired on the node (the
        # builder flow consumes the same SignedValidatorRegistration
        # shape); otherwise retained for the flow to pick up
        builder = getattr(self.node, "builder", None)
        if builder is not None and registrations:
            await builder.register_validators(registrations)
        return {}

    def _decode_versioned(self, attr: str, raw: bytes):
        """Decode raw SSZ against each scheduled milestone's schema,
        newest first — strict decoding makes cross-family false
        positives fail, so the wire shape picks its own fork."""
        from ..spec.milestones import build_fork_schedule
        last = None
        for version in reversed(
                build_fork_schedule(self.node.spec.config).versions):
            try:
                return getattr(version.schemas, attr).deserialize(raw)
            except Exception as exc:
                last = exc
        raise HttpError(400, f"malformed {attr}: {last}")

    async def _submit_attestations(self, body=None, raw_body=None):
        if body is None and raw_body:
            # SSZ alternative (application/octet-stream): ONE
            # attestation per request, the remote VC's submit shape
            # (electra wire = SingleAttestation); the shared codec
            # policy disambiguates by slot
            from ..spec.codec import deserialize_attestation_wire
            try:
                att = deserialize_attestation_wire(
                    self.node.spec.config, raw_body,
                    self.node.chain.current_slot())
            except Exception as exc:
                raise HttpError(400, f"malformed attestation: {exc}")
            if self.validator_api is not None:
                await self.validator_api.publish_attestation(att)
                return {}
            if hasattr(att, "attester_index"):
                from ..node.validators import normalize_attestation
                try:
                    # same advanced state the gossip path uses: the
                    # committee shuffle needs the slot's epoch applied
                    state = self.node.advanced_head_state(
                        min(att.data.slot,
                            self.node.chain.current_slot()))
                except Exception:
                    raise HttpError(503, "no state for this slot yet")
                att = normalize_attestation(self.node.spec, state, att)
                if att is None:
                    raise HttpError(400, "attester not in committee")
            from ..node.gossip import ValidationResult
            result = await self.node.attestation_validator.validate(att)
            if result is ValidationResult.REJECT:
                raise HttpError(400, "attestation rejected")
            self.node.attestation_manager.add_attestation(att)
            return {}
        if not isinstance(body, list):
            raise HttpError(400, "expected a list of attestations")
        S = self.node.spec.schemas
        from ..spec.datastructures import AttestationData, Checkpoint
        accepted = 0
        for a in body:
            try:
                data = a["data"]
                att = S.Attestation(
                    aggregation_bits=S.Attestation._ssz_fields[
                        "aggregation_bits"].deserialize(
                        bytes.fromhex(a["aggregation_bits"][2:])),
                    data=AttestationData(
                        slot=int(data["slot"]),
                        index=int(data["index"]),
                        beacon_block_root=bytes.fromhex(
                            data["beacon_block_root"][2:]),
                        source=Checkpoint(
                            epoch=int(data["source"]["epoch"]),
                            root=bytes.fromhex(data["source"]["root"][2:])),
                        target=Checkpoint(
                            epoch=int(data["target"]["epoch"]),
                            root=bytes.fromhex(data["target"]["root"][2:]))),
                    signature=bytes.fromhex(a["signature"][2:]))
            except (KeyError, ValueError, TypeError, AttributeError) as exc:
                raise HttpError(400, f"malformed attestation: {exc}")
            result = await self.node.attestation_validator.validate(att)
            from ..node.gossip import ValidationResult
            if result is ValidationResult.ACCEPT:
                self.node.attestation_manager.add_attestation(att)
                accepted += 1
        return {"data": {"accepted": accepted}}

    async def _submit_exit(self, body=None):
        from ..spec.datastructures import (SignedVoluntaryExit,
                                           VoluntaryExit)
        try:
            msg = body["message"]
            exit_op = SignedVoluntaryExit(
                message=VoluntaryExit(
                    epoch=int(msg["epoch"]),
                    validator_index=int(msg["validator_index"])),
                signature=bytes.fromhex(
                    body["signature"].removeprefix("0x")))
        except (KeyError, ValueError, TypeError, AttributeError) as exc:
            raise HttpError(400, f"malformed exit: {exc}")
        pool = self.node.operation_pools["voluntary_exits"]
        if not pool.add(self.node.chain.head_state(), exit_op):
            raise HttpError(400, "exit invalid or duplicate")
        from ..node.gossip import VOLUNTARY_EXIT_TOPIC
        from ..spec.datastructures import SignedVoluntaryExit as SVE
        await self.node.gossip.publish(
            VOLUNTARY_EXIT_TOPIC, SVE.serialize(exit_op))
        return {}

    # -- op-pool family (generic SSZ<->JSON via the schema walk) -------
    def _pool_json(self, pool_name: str):
        return {"data": [
            _ssz_to_json(type(op), op)
            for op in self.node.operation_pools[pool_name].get_for_block(
                10 ** 9)]}

    async def _get_pool_exits(self):
        return self._pool_json("voluntary_exits")

    async def _get_attester_slashings(self):
        return self._pool_json("attester_slashings")

    async def _get_proposer_slashings(self):
        return self._pool_json("proposer_slashings")

    async def _get_bls_changes(self):
        return self._pool_json("bls_to_execution_changes")

    def _head_version_name(self) -> str:
        from ..spec.milestones import build_fork_schedule
        v = build_fork_schedule(self.node.spec.config).version_at_slot(
            self.node.chain.head_slot())
        return v.milestone.name.lower()

    async def _get_attester_slashings_v2(self):
        return {"version": self._head_version_name(),
                **self._pool_json("attester_slashings")}

    async def _get_proposer_slashings_v2(self):
        return {"version": self._head_version_name(),
                **self._pool_json("proposer_slashings")}

    async def _submit_op(self, pool_name: str, schema, topic, body):
        """Shared POST path: parse via the schema walk, validate by
        pool entry (the apply rule), gossip on accept (reference
        statetransition/OperationPool.java add + publish)."""
        try:
            op = _ssz_from_json(schema, body)
        except (KeyError, ValueError, TypeError, AttributeError) as exc:
            raise HttpError(400, f"malformed {pool_name[:-1]}: {exc}")
        pool = self.node.operation_pools[pool_name]
        if not pool.add(self.node.chain.head_state(), op):
            raise HttpError(400,
                            f"{pool_name[:-1]} invalid or duplicate")
        await self.node.gossip.publish(topic, type(op).serialize(op))
        return {}

    async def _post_attester_slashing(self, body=None):
        from ..node.gossip import ATTESTER_SLASHING_TOPIC
        S = self.node.spec.at_slot(self.node.chain.head_slot()).schemas
        return await self._submit_op(
            "attester_slashings", S.AttesterSlashing,
            ATTESTER_SLASHING_TOPIC, body)

    async def _post_proposer_slashing(self, body=None):
        from ..node.gossip import PROPOSER_SLASHING_TOPIC
        S = self.node.spec.at_slot(self.node.chain.head_slot()).schemas
        return await self._submit_op(
            "proposer_slashings", S.ProposerSlashing,
            PROPOSER_SLASHING_TOPIC, body)

    async def _post_bls_changes(self, body=None):
        """Per-item semantics (standard API): every valid change is
        pooled + broadcast; failures are reported per index, and one
        duplicate must not abort the rest of the batch."""
        from ..node.gossip import BLS_TO_EXECUTION_CHANGE_TOPIC
        from ..spec.milestones import build_fork_schedule, SpecMilestone
        try:
            version = build_fork_schedule(
                self.node.spec.config).version_for(SpecMilestone.CAPELLA)
        except KeyError:
            raise HttpError(400, "capella not scheduled on this network")
        ops = body if isinstance(body, list) else [body]
        failures = []
        for i, op in enumerate(ops):
            try:
                await self._submit_op(
                    "bls_to_execution_changes",
                    version.schemas.SignedBLSToExecutionChange,
                    BLS_TO_EXECUTION_CHANGE_TOPIC, op)
            except HttpError as exc:
                failures.append({"index": i, "message": exc.message})
        if failures:
            raise HttpError(400, f"failures: {failures}")
        return {}

    # -- balances / roots / withdrawals --------------------------------
    async def _validator_balances(self, state_id: str, query=None):
        state = await self._resolve_state_async(state_id)
        ids = None
        if query and query.get("id"):
            # the standard API allows index OR pubkey ids
            ids = self._validator_indices(state,
                                          query["id"].split(","))
        return self._balances_json(state, ids)

    async def _validator_balances_post(self, state_id: str, body=None):
        state = await self._resolve_state_async(state_id)
        ids = self._validator_indices(state, body) \
            if isinstance(body, list) else None
        return self._balances_json(state, ids)

    def _balances_json(self, state, ids):
        n = len(state.balances)
        idx = range(n) if ids is None else ids
        out = []
        for i in idx:
            if not 0 <= i < n:
                raise HttpError(400, f"unknown validator index {i}")
            out.append({"index": str(i),
                        "balance": str(state.balances[i])})
        return {"data": out}

    async def _block_root(self, block_id: str):
        return {"data": {"root": _hex(self._resolve_block_root(
            block_id))}}

    async def _block_attestations(self, block_id: str):
        block = self._block_by_root(self._resolve_block_root(block_id))
        if block is None:
            raise HttpError(404, "block not found")
        body = block.message.body if hasattr(block, "message") else \
            block.body
        return {"data": [_ssz_to_json(type(a), a)
                         for a in body.attestations]}

    async def _peer_count(self):
        connected = 0
        if self.networked:
            connected = sum(1 for p in self.networked.net.peers
                            if p.connected)
        return {"data": {"disconnected": "0", "connecting": "0",
                         "connected": str(connected),
                         "disconnecting": "0"}}

    async def _expected_withdrawals(self, state_id: str, query=None):
        state = await self._resolve_state_async(state_id)
        if not hasattr(state, "next_withdrawal_index"):
            raise HttpError(400, "pre-capella state has no withdrawals")
        cfg = self.node.spec.config
        try:
            slot = int(query["proposal_slot"]) if query \
                and query.get("proposal_slot") else state.slot + 1
        except (ValueError, TypeError):
            raise HttpError(400, "invalid proposal_slot")
        # the advance is client-controlled work on the event loop:
        # bound it to one epoch ahead (the reference's handler serves
        # proposal lookahead, not arbitrary time travel)
        if not (state.slot <= slot
                <= state.slot + cfg.SLOTS_PER_EPOCH):
            raise HttpError(400, "proposal_slot out of range "
                                 "(within one epoch of the state)")
        from ..spec.transition import process_slots
        if state.slot < slot:
            state = process_slots(cfg, state, slot)
        if hasattr(state, "pending_partial_withdrawals"):
            from ..spec.electra.block import get_expected_withdrawals
            withdrawals = get_expected_withdrawals(cfg, state)[0]
        else:
            from ..spec.capella.block import get_expected_withdrawals
            withdrawals = get_expected_withdrawals(cfg, state)
        return {"data": [{
            "index": str(w.index),
            "validator_index": str(w.validator_index),
            "address": _hex(w.address),
            "amount": str(w.amount)} for w in withdrawals]}

    # -- metrics -------------------------------------------------------
    async def _submit_sync_messages(self, body=None):
        """Sync-committee messages (reference handlers/v1/beacon/
        PostSyncCommittees) — the remote VC's sync-duty submission."""
        if not isinstance(body, list):
            raise HttpError(400, "expected a list of sync messages")
        from ..spec.milestones import build_fork_schedule, SpecMilestone
        try:
            version = build_fork_schedule(
                self.node.spec.config).version_for(SpecMilestone.ALTAIR)
        except KeyError:
            raise HttpError(400, "altair not scheduled on this network")
        # parse the WHOLE batch before publishing anything: a 400 must
        # not leave earlier messages already gossiped
        msgs = []
        for m in body:
            try:
                msgs.append(version.schemas.SyncCommitteeMessage(
                    slot=int(m["slot"]),
                    beacon_block_root=bytes.fromhex(
                        m["beacon_block_root"][2:]),
                    validator_index=int(m["validator_index"]),
                    signature=bytes.fromhex(m["signature"][2:])))
            except (KeyError, ValueError, TypeError) as exc:
                raise HttpError(400, f"malformed sync message: {exc}")
        for msg in msgs:
            if self.validator_api is not None:
                await self.validator_api.publish_sync_committee_message(
                    msg)
            else:
                await self.node._process_sync_message(msg)
        return {"accepted": len(msgs)}

    async def _events(self, query=None):
        """SSE events stream (reference: handlers/v1/events/GetEvents +
        EventSubscriptionManager): head / block / finalized_checkpoint
        topics, one subscriber per connection, detached on close."""
        import asyncio as _asyncio
        from ..infra.events import (BlockImportChannel, ChainHeadChannel,
                                    FinalizedCheckpointChannel)
        from ..infra.restapi import SseStream
        topics = set((query or {}).get(
            "topics", "head,block,finalized_checkpoint").split(","))
        known = {"head", "block", "finalized_checkpoint"}
        if not topics <= known:
            raise HttpError(400, f"unknown topics {topics - known}")
        queue: _asyncio.Queue = _asyncio.Queue(maxsize=256)

        def _offer(item):
            try:
                queue.put_nowait(item)
            except _asyncio.QueueFull:
                pass    # slow client: drop rather than grow unbounded

        api = self

        class _Sink:
            def on_block_imported(self, signed_block, post_state):
                if "block" not in topics:
                    return
                block = signed_block.message
                _offer(("block", {
                    "slot": str(block.slot),
                    "block": _hex(block.htr()),
                    "execution_optimistic": False}))

            def on_chain_head_updated(self, slot, root, reorg=False):
                # FORK-CHOICE head changes only — an imported
                # non-canonical block must not masquerade as head
                if "head" not in topics:
                    return
                block = api.node.store.blocks.get(root)
                cfg = api.node.spec.config
                # duty dependent roots: last block before the epoch's
                # (and previous epoch's) first slot — consumers refetch
                # duties when these change across a reorg
                prev_dep = cur_dep = bytes(32)
                try:
                    from ..spec import helpers as _H
                    state = api.node.chain.head_state()
                    epoch = slot // cfg.SLOTS_PER_EPOCH
                    cur_start = epoch * cfg.SLOTS_PER_EPOCH
                    prev_start = max(epoch - 1, 0) * cfg.SLOTS_PER_EPOCH
                    if cur_start > 0:
                        cur_dep = _H.get_block_root_at_slot(
                            cfg, state, cur_start - 1)
                    if prev_start > 0:
                        prev_dep = _H.get_block_root_at_slot(
                            cfg, state, prev_start - 1)
                except Exception:
                    pass
                _offer(("head", {
                    "slot": str(slot), "block": _hex(root),
                    "state": _hex(block.state_root)
                    if block is not None else _hex(bytes(32)),
                    "epoch_transition": slot
                    % cfg.SLOTS_PER_EPOCH == 0,
                    "previous_duty_dependent_root": _hex(prev_dep),
                    "current_duty_dependent_root": _hex(cur_dep),
                    "execution_optimistic": False}))

            def on_new_finalized_checkpoint(self, checkpoint,
                                            from_optimistic_api=False):
                if "finalized_checkpoint" in topics:
                    _offer(("finalized_checkpoint", {
                        "block": _hex(checkpoint.root),
                        "epoch": str(checkpoint.epoch),
                        "execution_optimistic": False}))

        channels = self.node.channels

        async def gen():
            # subscribe INSIDE the generator so attach/detach are
            # symmetric: a stream torn down before its first event
            # (or never started at all) leaves no dead sink behind
            sink = _Sink()
            channels.subscribe(BlockImportChannel, sink)
            channels.subscribe(ChainHeadChannel, sink)
            channels.subscribe(FinalizedCheckpointChannel, sink)
            try:
                while True:
                    yield await queue.get()
            finally:
                channels.unsubscribe(BlockImportChannel, sink)
                channels.unsubscribe(ChainHeadChannel, sink)
                channels.unsubscribe(FinalizedCheckpointChannel, sink)

        return SseStream(gen())

    # -- light client (reference: handlers/v1/beacon/lightclient/) -----
    @staticmethod
    def _lc_header_json(header):
        return {"beacon": {
            "slot": str(header.slot),
            "proposer_index": str(header.proposer_index),
            "parent_root": _hex(header.parent_root),
            "state_root": _hex(header.state_root),
            "body_root": _hex(header.body_root)}}

    @staticmethod
    def _lc_committee_json(committee):
        return {"pubkeys": [_hex(pk) for pk in committee.pubkeys],
                "aggregate_pubkey": _hex(committee.aggregate_pubkey)}

    async def _lc_bootstrap(self, block_id: str):
        from ..spec.altair.light_client import create_bootstrap
        root = self._resolve_block_root(block_id)
        block = self.node.store.blocks.get(root)
        state = self.node.store.block_states.get(root)
        if block is None or state is None:
            raise HttpError(404, "block/state not retained")
        if not hasattr(state, "current_sync_committee"):
            raise HttpError(400, "pre-altair state has no light client")
        b = create_bootstrap(self.node.spec.config, state, block)
        return {"data": {
            "header": self._lc_header_json(b.header),
            "current_sync_committee": self._lc_committee_json(
                b.current_sync_committee),
            "current_sync_committee_branch": [
                _hex(h) for h in b.current_sync_committee_branch]}}

    async def _lc_finality_update(self):
        """Latest finality-bearing update derivable from the hot chain:
        newest (attested, child-with-aggregate) pair whose attested
        state names a known finalized block."""
        from ..spec.altair.light_client import (block_to_header,
                                                create_update)
        store = self.node.store
        cfg = self.node.spec.config
        root = self.node.chain.head_root
        for _ in range(2 * cfg.SLOTS_PER_EPOCH):
            blk = store.blocks.get(root)
            if blk is None or not hasattr(blk.body, "sync_aggregate"):
                break
            parent = blk.parent_root
            pblk = store.blocks.get(parent)
            pstate = store.block_states.get(parent)
            agg = blk.body.sync_aggregate
            if (pblk is not None and pstate is not None
                    and pblk.slot == blk.slot - 1
                    and sum(agg.sync_committee_bits) > 0):
                fin_root = pstate.finalized_checkpoint.root
                fin_blk = store.blocks.get(fin_root)
                if fin_blk is not None:
                    u = create_update(
                        cfg, pstate, pblk, block_to_header(fin_blk),
                        agg, blk.slot, include_next_committee=False)
                    return {"data": {
                        "attested_header": self._lc_header_json(
                            u.attested_header),
                        "finalized_header": self._lc_header_json(
                            u.finalized_header),
                        "finality_branch": [
                            _hex(h) for h in u.finality_branch],
                        "sync_aggregate": {
                            # packed SSZ bitvector hex, per the API spec
                            "sync_committee_bits": _hex(
                                type(agg)._ssz_fields[
                                    "sync_committee_bits"].serialize(
                                    agg.sync_committee_bits)),
                            "sync_committee_signature": _hex(
                                agg.sync_committee_signature)},
                        "signature_slot": str(u.signature_slot)}}
            root = parent
        raise HttpError(404, "no finality update available")

    async def _lc_updates(self, query=None):
        """GetLightClientUpdatesByRange: best retained update per sync
        committee period (reference handlers/v1/beacon/
        GetLightClientUpdatesByRange) — served from the hot chain, so
        only recently-retained periods resolve."""
        from ..spec.altair.light_client import (block_to_header,
                                                create_update)
        try:
            start = int(query.get("start_period", 0)) if query else 0
            count = min(int(query.get("count", 1)) if query else 1, 128)
        except (ValueError, TypeError, KeyError):
            raise HttpError(400, "invalid start_period/count")
        store = self.node.store
        cfg = self.node.spec.config
        period_slots = (cfg.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
                        * cfg.SLOTS_PER_EPOCH)
        best_by_period: dict = {}
        root = self.node.chain.head_root
        for _ in range(4 * cfg.SLOTS_PER_EPOCH):
            blk = store.blocks.get(root)
            if blk is None or not hasattr(blk.body, "sync_aggregate"):
                break
            parent = blk.parent_root
            pblk = store.blocks.get(parent)
            pstate = store.block_states.get(parent)
            agg = blk.body.sync_aggregate
            if (pblk is not None and pstate is not None
                    and sum(agg.sync_committee_bits) > 0):
                period = pblk.slot // period_slots
                fin_blk = store.blocks.get(
                    pstate.finalized_checkpoint.root)
                prev = best_by_period.get(period)
                # "best" per the spec's is_better_update ordering
                # proxy: finality-bearing beats not, then highest
                # sync-committee participation
                rank = (fin_blk is not None,
                        sum(agg.sync_committee_bits))
                if (start <= period < start + count
                        and (prev is None or rank > prev[2])):
                    u = create_update(
                        cfg, pstate, pblk,
                        block_to_header(fin_blk)
                        if fin_blk is not None else None,
                        agg, blk.slot)
                    best_by_period[period] = (u, agg, rank)
            root = parent
        # the API schema requires these fields populated; a zeroed
        # header marks "no finality proof in this update"
        zero_header = {"beacon": {
            "slot": "0", "proposer_index": "0",
            "parent_root": _hex(bytes(32)),
            "state_root": _hex(bytes(32)),
            "body_root": _hex(bytes(32))}}
        out = []
        for period in sorted(best_by_period):
            u, agg, _rank = best_by_period[period]
            out.append({"data": {
                "attested_header": self._lc_header_json(
                    u.attested_header),
                "next_sync_committee": self._lc_committee_json(
                    u.next_sync_committee)
                if u.next_sync_committee is not None else None,
                "next_sync_committee_branch": [
                    _hex(h) for h in u.next_sync_committee_branch],
                "finalized_header": self._lc_header_json(
                    u.finalized_header)
                if u.finalized_header is not None else zero_header,
                "finality_branch": [_hex(h)
                                    for h in u.finality_branch],
                "sync_aggregate": {
                    "sync_committee_bits": _hex(
                        type(agg)._ssz_fields[
                            "sync_committee_bits"].serialize(
                            agg.sync_committee_bits)),
                    "sync_committee_signature": _hex(
                        agg.sync_committee_signature)},
                "signature_slot": str(u.signature_slot)}})
        return out

    async def _peer_by_id(self, peer_id: str):
        """reference handlers/v1/node/GetPeerById."""
        if self.networked:
            for peer in self.networked.net.peers:
                if peer.node_id.hex() == peer_id.removeprefix("0x"):
                    return {"data": self._peer_json(peer)}
        raise HttpError(404, "peer not found")

    async def _debug_fork_choice(self):
        """reference handlers/v1/debug/GetForkChoice: the proto-array
        dump fork-choice debugging tools consume."""
        store = self.node.store
        nodes = []
        for n in store.proto.nodes:
            nodes.append({
                "slot": str(n.slot),
                "block_root": _hex(n.root),
                "parent_root": _hex(store.proto.nodes[n.parent].root)
                if n.parent is not None else _hex(bytes(32)),
                "justified_epoch": str(n.justified_epoch),
                "finalized_epoch": str(n.finalized_epoch),
                # RAW weight: this endpoint exists to expose
                # vote-accounting state, including corrupt (negative)
                # values a clamp would hide
                "weight": str(n.weight),
                "validity": "valid",
                "execution_block_hash": _hex(bytes(32)),
            })
        return {
            "justified_checkpoint": {
                "epoch": str(store.justified_checkpoint.epoch),
                "root": _hex(store.justified_checkpoint.root)},
            "finalized_checkpoint": {
                "epoch": str(store.finalized_checkpoint.epoch),
                "root": _hex(store.finalized_checkpoint.root)},
            "fork_choice_nodes": nodes,
            "extra_data": {},
        }

    async def _admin_traces(self, query=None):
        """The slow-trace ring as JSON: the N slowest complete verifies
        with their per-stage latency breakdowns (ms), slowest first.
        `?clear=1` empties the ring after the read — useful for
        isolating one incident's traces from boot-time compiles."""
        out = {"tracing_enabled": tracing.enabled(),
               "data": tracing.slow_traces()}
        if query and query.get("clear") in ("1", "true"):
            tracing.clear_slow_traces()
        return out

    async def _metrics(self):
        return GLOBAL_REGISTRY.expose(), "text/plain; version=0.0.4"
