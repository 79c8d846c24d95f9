"""Remote validator client: duties over the REST API against a live
beacon node — blocks proposed, attestations and aggregates submitted,
chain justifies, and the remote chain matches in-process behavior."""

import asyncio

import pytest

from teku_tpu.api import BeaconRestApi
from teku_tpu.infra.service import ServiceController
from teku_tpu.node.gossip import InMemoryGossipNetwork
from teku_tpu.node.node import BeaconNode
from teku_tpu.spec import create_spec
from teku_tpu.spec.genesis import interop_genesis
from teku_tpu.validator import (BeaconNodeValidatorApi, LocalSigner,
                                RemoteValidatorApi,
                                SlashingProtectedSigner, ValidatorClient)
from teku_tpu.validator.slashing_protection import SlashingProtector


@pytest.mark.slow
def test_remote_vc_drives_chain_to_justification():
    # altair at genesis: the remote VC also exercises the
    # sync-committee submission endpoint
    import dataclasses
    from teku_tpu.spec import config as C
    from teku_tpu.spec import Spec
    spec = Spec(dataclasses.replace(C.MINIMAL, ALTAIR_FORK_EPOCH=0))
    state, sks = interop_genesis(spec.config, 16)

    async def run():
        net = InMemoryGossipNetwork()
        node = BeaconNode(spec, state, net.endpoint())
        api = BeaconRestApi(node,
                            validator_api=BeaconNodeValidatorApi(node))
        controller = ServiceController([node], "remote-vc-test")
        await controller.start()
        await api.start()
        try:
            remote = RemoteValidatorApi(
                spec, f"http://127.0.0.1:{api.port}")
            # record every fetch: the remote VC must live off duty
            # endpoints, never the debug state download (mainnet states
            # are hundreds of MB — round-3 review, weak #2)
            fetched = []
            orig_bytes = remote._get_bytes
            orig_json = remote._get_json

            def rec_bytes(path, _o=orig_bytes):
                data = _o(path)
                fetched.append((path, len(data)))
                return data

            def rec_json(path, _o=orig_json):
                out = _o(path)
                fetched.append((path, 0))
                return out
            remote._get_bytes = rec_bytes
            remote._get_json = rec_json
            signer = SlashingProtectedSigner(
                LocalSigner(dict(enumerate(sks))), SlashingProtector())
            client = ValidatorClient(spec, remote, signer,
                                     list(range(16)))
            loop = asyncio.get_running_loop()
            epochs = 3
            for slot in range(1, epochs * spec.config.SLOTS_PER_EPOCH + 1):
                await node.on_slot(slot)
                # the remote VC is its own process in production; here
                # each duty phase runs in a worker thread (own loop) so
                # its blocking HTTP can be served by THIS loop
                for phase in (client.on_slot_start,
                              client.on_attestation_due,
                              client.on_sync_committee_due,
                              client.on_aggregation_due):
                    await loop.run_in_executor(
                        None, lambda p=phase: asyncio.run(p(slot)))
            assert client.blocks_proposed \
                >= epochs * spec.config.SLOTS_PER_EPOCH - 1
            assert client.attestations_sent > 0
            assert node.chain.head_slot() \
                >= epochs * spec.config.SLOTS_PER_EPOCH - 1
            assert node.store.justified_checkpoint.epoch >= 1
            # the remote sync-aggregation duty used the REST
            # contribution endpoints: contributions reached the pool
            contrib_keys = [k for k in node.sync_pool._msgs
                            if isinstance(k, tuple)
                            and k and k[0] == "contrib"]
            assert contrib_keys, "no remote contributions pooled"
            # no beacon state ever crossed the wire: no debug-state
            # fetch, and every GET stayed wire-light (blocks, duties,
            # attestation data — never a state-sized body)
            assert fetched, "nothing recorded"
            assert not any("/debug/" in p for p, _ in fetched)
            assert max(n for _, n in fetched) < 100_000, \
                "a state-sized body crossed the wire"
        finally:
            await api.stop()
            await controller.stop()

    asyncio.run(run())
