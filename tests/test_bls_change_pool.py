"""bls_to_execution_changes: pool, gossip, block packing, REST family.

The done-criterion scenario: on a capella devnet a submitted
bls-change enters the pool (entry-validated, the reference's
SignedBlsToExecutionChangeValidator semantics), is packed into a
proposal, executes on-chain (credentials flip to 0x01), and is pruned
from the pool (reference: statetransition/OperationPool.java +
handlers/v1/beacon/PostBlsToExecutionChanges).
"""

import asyncio
import dataclasses
import json
import urllib.error
import urllib.request

import pytest

from teku_tpu.api import BeaconRestApi
from teku_tpu.crypto import bls
from teku_tpu.node import Devnet
from teku_tpu.spec import config as C, Spec
from teku_tpu.spec import helpers as H
from teku_tpu.spec.capella.datastructures import get_capella_schemas

CFG = dataclasses.replace(C.MINIMAL, ALTAIR_FORK_EPOCH=0,
                          BELLATRIX_FORK_EPOCH=0, CAPELLA_FORK_EPOCH=0)


def _signed_change(cfg, state, sks, idx, address=b"\xcc" * 20):
    S = get_capella_schemas(cfg)
    change = S.BLSToExecutionChange(
        validator_index=idx,
        from_bls_pubkey=bls.secret_to_public_key(sks[idx]),
        to_execution_address=address)
    domain = H.compute_domain(C.DOMAIN_BLS_TO_EXECUTION_CHANGE,
                              cfg.GENESIS_FORK_VERSION,
                              state.genesis_validators_root)
    sig = bls.sign(sks[idx], H.compute_signing_root(change, domain))
    return S.SignedBLSToExecutionChange(message=change, signature=sig)


@pytest.mark.slow
def test_bls_change_lands_in_block_via_rest():
    spec = Spec(CFG)
    net = Devnet(n_nodes=1, n_validators=16, spec=spec)
    node = net.nodes[0]
    state = net.genesis_state
    # the interop keys are deterministic — rebuild the signer's view
    from teku_tpu.spec.genesis import interop_secret_keys
    sks = interop_secret_keys(16)
    signed = _signed_change(CFG, state, sks, idx=5)

    async def run():
        await net.start()
        api = BeaconRestApi(node)
        await api.start()
        try:
            base = f"http://127.0.0.1:{api.port}"
            loop = asyncio.get_running_loop()

            def _post(path, payload):
                req = urllib.request.Request(
                    base + path, data=json.dumps(payload).encode(),
                    method="POST",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    return json.loads(r.read())

            def _get(path):
                with urllib.request.urlopen(base + path,
                                            timeout=10) as r:
                    return json.loads(r.read())

            async def post(path, payload):
                return await loop.run_in_executor(None, _post, path,
                                                  payload)

            async def get(path):
                return await loop.run_in_executor(None, _get, path)

            payload = [{
                "message": {
                    "validator_index": "5",
                    "from_bls_pubkey":
                        "0x" + bls.secret_to_public_key(sks[5]).hex(),
                    "to_execution_address": "0x" + "cc" * 20},
                "signature": "0x" + bytes(signed.signature).hex()}]
            await post("/eth/v1/beacon/pool/bls_to_execution_changes",
                       payload)
            pool = node.operation_pools["bls_to_execution_changes"]
            assert len(pool) == 1
            listed = await get(
                "/eth/v1/beacon/pool/bls_to_execution_changes")
            assert listed["data"][0]["message"]["validator_index"] == "5"
            # duplicate submission is a 400
            with pytest.raises(urllib.error.HTTPError):
                await post(
                    "/eth/v1/beacon/pool/bls_to_execution_changes",
                    payload)
            # run a few slots: the next proposal must pack + execute it
            await net.run_until_slot(4)
            head = node.chain.head_state()
            creds = head.validators[5].withdrawal_credentials
            assert creds[:1] == b"\x01" and creds[12:] == b"\xcc" * 20
            assert len(pool) == 0          # pruned on inclusion
            # it rode in an actual block body
            found = any(
                len(node.store.blocks[root].body
                    .bls_to_execution_changes) > 0
                for root in node.store.blocks
                if hasattr(node.store.blocks[root].body,
                           "bls_to_execution_changes"))
            assert found
        finally:
            await api.stop()
            await net.stop()

    asyncio.run(run())


@pytest.mark.slow
def test_pool_rest_family_and_balances():
    spec = Spec(CFG)
    net = Devnet(n_nodes=1, n_validators=16, spec=spec)
    node = net.nodes[0]

    async def run():
        await net.start()
        api = BeaconRestApi(node)
        await api.start()
        try:
            await net.run_until_slot(2)
            base = f"http://127.0.0.1:{api.port}"
            loop = asyncio.get_running_loop()

            def _get(path):
                with urllib.request.urlopen(base + path,
                                            timeout=10) as r:
                    return json.loads(r.read())

            async def get(path):
                return await loop.run_in_executor(None, _get, path)

            # empty pools serve empty lists
            for name in ("attester_slashings", "proposer_slashings",
                         "voluntary_exits"):
                empty = await get(f"/eth/v1/beacon/pool/{name}")
                assert empty["data"] == []
            # v2 pool family: versioned envelope
            for name in ("attester_slashings", "proposer_slashings"):
                v2 = await get(f"/eth/v2/beacon/pool/{name}")
                assert v2["data"] == []
                assert v2["version"] in (
                    "phase0", "altair", "bellatrix", "capella",
                    "deneb", "electra")
            # balances: full + filtered
            bal = await get(
                "/eth/v1/beacon/states/head/validator_balances")
            assert len(bal["data"]) == 16
            one = await get(
                "/eth/v1/beacon/states/head/validator_balances?id=3")
            assert one["data"][0]["index"] == "3"
            assert int(one["data"][0]["balance"]) > 0
            # block root + attestations + peer count
            root = (await get("/eth/v1/beacon/blocks/head/root")
                    )["data"]["root"]
            assert root.startswith("0x") and len(root) == 66
            atts = await get("/eth/v1/beacon/blocks/head/attestations")
            assert isinstance(atts["data"], list)
            pc = (await get("/eth/v1/node/peer_count"))["data"]
            assert pc["connected"] == "0"
            # expected withdrawals on a capella state
            w = await get(
                "/eth/v1/beacon/states/head/expected_withdrawals")
            assert isinstance(w["data"], list)
        finally:
            await api.stop()
            await net.stop()

    asyncio.run(run())
