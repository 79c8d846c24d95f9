"""Command-line entry point: subcommands + layered configuration.

Equivalent of the reference's CLI layer (reference: teku/src/main/java/
tech/pegasys/teku/Teku.java:37, cli/BeaconNodeCommand.java with
CLI > env (TEKU_*) > YAML layering via CascadingParamsProvider, and the
cli/subcommand/ family — node, validator-client, transition, genesis,
slashing-protection, peer): here argparse subcommands with the same
precedence rules (flags beat TEKU_TPU_* env vars beat --config-file
YAML beat defaults).

Run as `python -m teku_tpu.cli <subcommand>`.
"""

import argparse
import asyncio
import json
import logging
import os
import sys
from pathlib import Path
from typing import Any, Dict, Optional

from .infra.env import env_raw, env_str
from .infra.logs import configure as configure_logging

ENV_PREFIX = "TEKU_TPU_"


def layered_value(name: str, cli_value, yaml_cfg: Dict[str, Any],
                  default=None, cast=str):
    """CLI > env > YAML > default (reference CascadingParamsProvider).

    env_raw, not a typed helper: "unset" must stay distinguishable
    from every real value so YAML and defaults cascade beneath, and a
    malformed value fails flag validation loudly at boot with the
    operator present — the one place typo-degrades is the wrong
    contract."""
    if cli_value is not None:
        return cli_value
    env = env_raw(ENV_PREFIX + name.upper().replace("-", "_"))
    if env is not None:
        return cast(env)
    if name in yaml_cfg:
        return cast(yaml_cfg[name])
    return default


def _load_yaml(path: Optional[str]) -> Dict[str, Any]:
    if not path:
        return {}
    import yaml
    with open(path) as f:
        out = yaml.safe_load(f) or {}
    if not isinstance(out, dict):
        raise SystemExit("config file must be a mapping")
    return out


# --------------------------------------------------------------------------
# subcommands
# --------------------------------------------------------------------------

def _configure_log_format(args, yaml_cfg) -> str:
    """Opt-in structured logging (`--log-format json` /
    TEKU_TPU_LOG_FORMAT): every record becomes one JSON object carrying
    the active trace id, so logs join slow traces and flight-recorder
    events on one correlation key.  Default stays the human-scannable
    text lines."""
    choice = str(layered_value(
        "log-format", getattr(args, "log_format", None), yaml_cfg,
        "text")).lower()
    if choice not in ("text", "json"):
        raise SystemExit(
            f"invalid --log-format {choice!r} (use text or json)")
    configure_logging(fmt=choice)
    return choice


def _configure_tracing(args, yaml_cfg) -> str:
    """Hot-path tracing switch (default on: spans cost ~a perf_counter
    pair each; `off` compiles them to shared no-ops for A/B runs)."""
    from .infra import tracing

    def norm(v):
        # YAML parses bare on/off as booleans; map them back
        if isinstance(v, bool):
            return "on" if v else "off"
        return str(v).lower()

    choice = layered_value("tracing", getattr(args, "tracing", None),
                           yaml_cfg, "on", cast=norm)
    if choice not in ("on", "off"):
        raise SystemExit(f"invalid --tracing {choice!r} (use on or off)")
    tracing.set_enabled(choice == "on")
    return choice


def _configure_overload(args, yaml_cfg) -> str:
    """Overload-control switch (default on): the node wires an
    AdmissionController — deadline-aware adaptive batching, priority
    classes with strict-priority drain, and shed-by-class brownout
    under SLO feedback (`services/admission.py`).  ``off`` restores the
    fixed max-batch drain and overflow-only shedding.  The thresholds
    themselves are env knobs (TEKU_TPU_BROWNOUT_*,
    TEKU_TPU_ADMISSION_*, TEKU_TPU_VERIFY_CLASS_*_DEADLINE_MS —
    README "Overload & priority classes")."""
    def norm(v):
        if isinstance(v, bool):
            return "on" if v else "off"
        return str(v).lower()

    choice = layered_value("overload-control",
                           getattr(args, "overload_control", None),
                           yaml_cfg, "on", cast=norm)
    if choice not in ("on", "off"):
        raise SystemExit(
            f"invalid --overload-control {choice!r} (use on or off)")
    # the env var is how the choice reaches BeaconNode (and every
    # devnet node constructed inside the process)
    os.environ["TEKU_TPU_OVERLOAD_CONTROL"] = choice
    return choice


# mirror of ops/mxu.py PATHS, spelled locally so the boot path never
# imports the ops package (whose __init__ imports jax) on the main
# thread — the env var is how the choice reaches the kernel layer
_MONT_PATHS = ("vpu", "mxu", "auto", "mxu-force")


def _validate_mesh(choice: str) -> str:
    """`--mesh {off,auto,N}`: off | auto | a positive device count.

    YAML parses bare off/on/no/yes as booleans before this layer sees
    them, so the boolean spellings normalize instead of failing boot
    (the mesh knob must never be able to fail a node)."""
    if choice in ("off", "auto"):
        return choice
    if choice in ("false", "no", "none", "0", ""):
        return "off"
    if choice in ("true", "on", "yes"):
        return "auto"
    try:
        n = int(choice)
        if n < 1:
            raise ValueError
    except ValueError:
        raise SystemExit(
            f"invalid --mesh {choice!r} (use off, auto, or a positive "
            "device count)")
    return str(n)


def _configure_kernel(args, yaml_cfg):
    """Kernel-layer knobs that must be decided BEFORE jax loads:

    - the mont_mul engine (`--mont-path` / TEKU_TPU_MONT_MUL: vpu |
      mxu | auto; auto = vpu until a chip measurement earns the int8
      digit-split MXU path its place) — resolved by ops/mxu.py at
      trace time in the probe/dispatch threads;
    - the persistent XLA compile cache (TEKU_TPU_XLA_CACHE_DIR, ON by
      default; =off disables) so warm boots load the multi-minute
      per-shape kernel compiles from disk instead of repaying them.

    Returns (mont_path, mesh).
    """
    from .infra import compilecache

    choice = str(layered_value(
        "mont-mul", getattr(args, "mont_path", None), yaml_cfg,
        "auto")).lower()
    if choice not in _MONT_PATHS:
        raise SystemExit(f"invalid --mont-path {choice!r} (use one of "
                         f"{'/'.join(_MONT_PATHS)})")
    os.environ["TEKU_TPU_MONT_MUL"] = choice
    # multi-chip mesh (`--mesh {off,auto,N}` / TEKU_TPU_MESH): resolved
    # to a device mesh by the loader's probe (teku_tpu/parallel — auto
    # takes the largest pow-2 <= available devices, a non-pow-2 or
    # over-sized N demotes with one WARN instead of failing boot).  An
    # EXPLICIT numeric N also forces N virtual host devices so a
    # CPU-fallback node (or devnet) genuinely shards — this XLA flag
    # must be set before jax loads, which is why it lives here; it
    # only affects the host platform, never real TPU device counts.
    mesh_choice = _validate_mesh(str(layered_value(
        "mesh", getattr(args, "mesh", None), yaml_cfg, "off")).lower())
    os.environ["TEKU_TPU_MESH"] = mesh_choice
    if mesh_choice not in ("off", "auto") and int(mesh_choice) > 1:
        from .infra.env import ensure_virtual_devices
        ensure_virtual_devices(int(mesh_choice))
    compilecache.configure()
    return choice, mesh_choice


def _configure_bls(args, yaml_cfg, *, supervise: bool = True,
                   mont_path=None, mesh=None, key_bucket=None):
    """Choose the BLS bring-up shape BEFORE any service starts.

    ``auto`` (the default) and ``supervised`` boot the node immediately
    on the pure oracle and return a BackendSupervisor the node runs in
    the background: device bring-up gets unbounded-but-observable
    patience instead of a 120 s probe that a minutes-long cold compile
    can never beat, and on READY the facade hot-swaps.
    ``jax`` keeps the reference-style hard preflight (Teku.java:74);
    ``pure`` opts out.  `key_bucket`: the aggregates' key bucket the
    supervisor warms (`_key_bucket`).  Returns (name,
    supervisor-or-None)."""
    from .crypto.bls import loader
    choice = layered_value("bls-impl", getattr(args, "bls_impl", None),
                           yaml_cfg, "auto")
    if choice in ("auto", "supervised") and supervise:
        loader.configure("supervised")      # oracle serves from slot 0
        supervisor = loader.make_supervisor(mont_path=mont_path,
                                            mesh=mesh,
                                            key_bucket=key_bucket)
        print("BLS implementation: pure (supervised device bring-up "
              "in background)")
        return "supervised", supervisor
    try:
        name = loader.configure("pure" if choice == "supervised"
                                else choice, mont_path=mont_path,
                                mesh=mesh)
    except loader.BlsLoadError as exc:
        raise SystemExit(f"BLS preflight failed: {exc}")
    where = "" if name == "pure" else f" on {loader.device_label()}"
    print(f"BLS implementation: {name}{where}")
    return name, None


def _key_bucket(spec, state) -> int:
    """The `kmax` bucket of the largest aggregate this node verifies,
    from its preset and its state's active validators
    (`shapeset.aggregate_key_bucket`; mainnet at ~1M validators: 512)."""
    from .ops import shapeset
    from .spec.helpers import get_active_validator_indices
    epoch = state.slot // spec.config.SLOTS_PER_EPOCH
    active = len(get_active_validator_indices(state, epoch))
    return shapeset.aggregate_key_bucket(spec.config, active)


def cmd_node(args) -> int:
    """Run a beacon node: p2p + REST + optional validators + storage."""
    from .networking import NetworkedNode
    from .api import BeaconRestApi
    from .spec import create_spec
    from .spec.genesis import interop_genesis
    from .storage.database import Database, PersistentChainStorage
    from .validator import (BeaconNodeValidatorApi, LocalSigner,
                            SlashingProtectedSigner, ValidatorClient)
    from .validator.slashing_protection import SlashingProtector

    yaml_cfg = _load_yaml(args.config_file)
    _configure_log_format(args, yaml_cfg)
    _configure_tracing(args, yaml_cfg)
    _configure_overload(args, yaml_cfg)
    # arm the crash path before anything can wedge: faulthandler file
    # + flight-recorder JSONL dump on fatal crash (infra/flightrecorder)
    from .infra import flightrecorder
    flightrecorder.install_crash_hooks()
    mont_path, mesh = _configure_kernel(args, yaml_cfg)
    network = layered_value("network", args.network, yaml_cfg, "minimal")
    port = int(layered_value("p2p-port", args.p2p_port, yaml_cfg, 0, int))
    rest_port = int(layered_value("rest-port", args.rest_port, yaml_cfg,
                                  5051, int))
    data_dir = layered_value("data-dir", args.data_dir, yaml_cfg)
    n_interop = int(layered_value("interop-validators",
                                  args.interop_validators, yaml_cfg, 0,
                                  int))
    total_interop = int(layered_value("interop-total",
                                      args.interop_total, yaml_cfg,
                                      max(n_interop, 64), int))

    import time
    spec = create_spec(network)
    genesis_time_cfg = int(layered_value(
        "genesis-time", args.genesis_time, yaml_cfg, 0, int))

    # an existing database wins: resume the persisted chain instead of
    # minting a fresh genesis that would orphan it (reference:
    # StorageBackedRecentChainData boot path)
    db = None
    storage = None
    restored = None
    storage_mode = layered_value("storage-mode", args.storage_mode,
                                 yaml_cfg, "prune")
    if storage_mode not in ("archive", "prune"):
        raise SystemExit(f"invalid storage-mode {storage_mode!r} "
                         "(use archive or prune)")
    if data_dir:
        Path(data_dir).mkdir(parents=True, exist_ok=True)
        db = Database(Path(data_dir) / "chain.db", spec,
                      mode=storage_mode)
        storage = PersistentChainStorage(db)
        restored = storage.restore_store(spec)
    from_db = restored is not None

    ckpt_url = layered_value("checkpoint-sync-url",
                             args.checkpoint_sync_url, yaml_cfg)
    if restored is not None:
        anchor_state = db.get_state(db.load_anchor()[0].htr())
        genesis_state = anchor_state
        genesis_time = restored.genesis_time
        sks = interop_genesis(spec.config, total_interop,
                              genesis_time)[1] if n_interop else []
        print(f"resumed from data dir: head slot "
              f"{restored.blocks[restored.get_head()].slot}")
    elif ckpt_url:
        from .node.checkpoint import checkpoint_sync_store
        restored = checkpoint_sync_store(spec, ckpt_url)
        anchor_root = restored.justified_checkpoint.root
        genesis_state = restored.block_states[anchor_root]
        genesis_time = restored.genesis_time
        sks = (interop_genesis(spec.config, total_interop,
                               genesis_time)[1] if n_interop else [])
        print(f"checkpoint-synced from {ckpt_url}: anchor slot "
              f"{genesis_state.slot}")
    else:
        # interop devnets anchor genesis at "now" unless pinned — every
        # node on the devnet must pass the SAME value to share a chain
        genesis_time = genesis_time_cfg or int(time.time())
        genesis_state, sks = interop_genesis(spec.config, total_interop,
                                             genesis_time)
    # the supervisor warms the key bucket of the aggregates this state's
    # committees and its preset's sync committee send
    _, bls_supervisor = _configure_bls(
        args, yaml_cfg, mont_path=mont_path, mesh=mesh,
        key_bucket=_key_bucket(spec, genesis_state))

    async def run():
        from .infra.events import FinalizedCheckpointChannel
        udp_port = layered_value("udp-discovery-port",
                                 args.udp_discovery_port, yaml_cfg)
        if args.bootnode and udp_port is None:
            raise SystemExit("--bootnode requires --udp-discovery-port"
                             " (use 0 for an ephemeral port)")
        nn = NetworkedNode(
            spec, genesis_state, port=port, store=restored,
            udp_discovery_port=(int(udp_port) if udp_port is not None
                                else None),
            bootnodes=args.bootnode or [])
        # the node owns the supervisor's lifecycle: bring-up starts
        # with the node and stops with it (node/node.py do_start/do_stop)
        nn.node.supervisor = bls_supervisor
        if db is not None:
            if not from_db:
                # fresh genesis OR checkpoint-synced anchor: persist it
                # so a restart resumes from here
                anchor = nn.node.store.blocks[
                    nn.node.store.justified_checkpoint.root]
                db.save_anchor(anchor,
                               nn.node.store.block_states[anchor.htr()])
            def _persist_import(root):
                storage.on_block_imported(
                    nn.node.store.signed_blocks[root],
                    nn.node.store.block_states[root])
                # verified wire sidecars outlive the in-memory pool:
                # persisted for DA-window serving, pruned by epoch
                sidecars = nn.node.blob_pool.wire_sidecars_for(root)
                if sidecars:
                    db.save_blob_sidecars(root, sidecars)
            nn.node.block_manager.on_imported.append(_persist_import)

            class _FinalizedSink:
                def on_new_finalized_checkpoint(self, checkpoint,
                                                from_optimistic_api=False):
                    storage.on_finalized(nn.node.store, checkpoint)
            nn.node.channels.subscribe(FinalizedCheckpointChannel,
                                       _FinalizedSink())

            from .infra.events import SlotEventsChannel
            from .storage.pruner import StoragePruner
            retention = layered_value("history-retention-epochs",
                                      args.history_retention_epochs,
                                      yaml_cfg)
            pruner = StoragePruner(
                db, spec.config,
                history_retention_epochs=(int(retention)
                                          if retention is not None
                                          else None))
            nn.node.blob_store = db      # req/resp DB fallback
            nn.node.storage_pruner = pruner

            class _PruneSink:
                def on_slot(self, slot):
                    pruner.on_slot(slot)
            nn.node.channels.subscribe(SlotEventsChannel, _PruneSink())
        await nn.start()
        eth1_task = None
        eth1_endpoint = layered_value("eth1-endpoint",
                                      args.eth1_endpoint, yaml_cfg)
        if eth1_endpoint:
            from .node.deposits import DepositProvider
            from .node.eth1 import (Eth1DepositFollower,
                                    JsonRpcEth1Provider)
            host, _, p = eth1_endpoint.rpartition(":")
            provider = DepositProvider(spec.config)
            follower = Eth1DepositFollower(
                provider,
                JsonRpcEth1Provider(host or "127.0.0.1", int(p)),
                follow_distance=int(layered_value(
                    "eth1-follow-distance", args.eth1_follow_distance,
                    yaml_cfg, 8, int)))
            nn.node.deposit_provider = provider
            eth1_task = asyncio.create_task(follower.run())
        api_channel = BeaconNodeValidatorApi(nn.node)
        rest_api = BeaconRestApi(nn.node, nn, port=rest_port,
                                 validator_api=api_channel,
                                 database=db)
        await rest_api.start()
        clients = []
        if n_interop:
            keys = {i: sks[i] for i in range(n_interop)}
            signer = SlashingProtectedSigner(
                LocalSigner(keys),
                SlashingProtector(Path(data_dir) / "slashing"
                                  if data_dir else None))
            clients.append(ValidatorClient(spec, api_channel, signer,
                                           sorted(keys)))
        for addr in args.peer or []:
            host, _, p = addr.rpartition(":")
            try:
                await nn.net.connect(host or "127.0.0.1", int(p))
            except OSError as exc:
                logging.warning("dial %s failed: %s", addr, exc)
        print(f"node up: p2p={nn.net.port} rest={rest_api.port} "
              f"validators={n_interop}/{total_interop}")
        # real-time slot loop
        try:
            while True:
                now = int(time.time())
                slot = max(0, (now - genesis_time)
                           // spec.config.SECONDS_PER_SLOT)
                if slot > 0:
                    await nn.node.on_slot(slot)
                    # joined late or fell behind: multipeer catch-up
                    # (gossiped blocks with unknown parents park in the
                    # pending pool; sync backfills the gap)
                    if nn.node.chain.head_slot() + 1 < slot \
                            and nn.net.peers:
                        try:
                            await nn.sync.run_until_synced(max_rounds=2)
                        except Exception:
                            logging.exception("catch-up sync failed")
                    for c in clients:
                        await c.on_slot_start(slot)
                    await asyncio.sleep(spec.config.SECONDS_PER_SLOT / 3)
                    for c in clients:
                        await c.on_attestation_due(slot)
                    await asyncio.sleep(spec.config.SECONDS_PER_SLOT / 3)
                    for c in clients:
                        await c.on_aggregation_due(slot)
                next_slot_time = genesis_time + (slot + 1) * \
                    spec.config.SECONDS_PER_SLOT
                await asyncio.sleep(max(0.1, next_slot_time - time.time()))
        finally:
            if eth1_task is not None:
                eth1_task.cancel()
            await rest_api.stop()
            await nn.stop()
            if db is not None:
                db.close()

    asyncio.run(run())
    return 0


def _hard_exit_if_virtual_devices(rc: int) -> None:
    """Devnet clean-shutdown guard (pre-existing issue, noted in PR
    10): with a FORCED virtual host device count
    (``--xla_force_host_platform_device_count``, the numeric ``--mesh
    N`` path), XLA's CPU client teardown can race Python interpreter
    finalization and segfault/abort AFTER all devnet work completed
    and the verdict was printed — turning a clean run into rc 134/139.
    Once jax has been imported under that flag, skip interpreter
    teardown entirely: flush the evidence, disarm faulthandler (its
    atexit hook would write to a closing file), and ``os._exit`` with
    the real verdict.  Nothing of value runs after this point — the
    flight recorder dumps on failure paths, the compile cache writes
    at compile time.

    Scope (``TEKU_TPU_DEVNET_HARD_EXIT``: auto|1|0): the guard is for
    STANDALONE CLI processes whose next act is exiting anyway.  An
    embedding process (the in-process pytest suite calls
    ``main(["devnet", ...])`` directly) must never be os._exit'ed out
    from under its caller — ``auto`` (default) skips whenever pytest
    is loaded; ``1`` forces, ``0`` disables."""
    mode = env_str("TEKU_TPU_DEVNET_HARD_EXIT", "auto")
    if mode in ("0", "off", "false"):
        return
    if mode != "1" and "pytest" in sys.modules:
        return
    if "jax" not in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        return
    try:
        import faulthandler
        faulthandler.disable()
    except Exception:
        pass
    logging.shutdown()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)


def cmd_devnet(args) -> int:
    """In-process devnet: N nodes, loopback gossip, fast clock."""
    from .node import Devnet

    _configure_log_format(args, {})
    _configure_tracing(args, {})
    _configure_overload(args, {})
    mont_path, mesh = _configure_kernel(args, {})
    _, bls_supervisor = _configure_bls(args, {}, mont_path=mont_path,
                                       mesh=mesh)

    async def run():
        net = Devnet(n_nodes=args.nodes, n_validators=args.validators)
        if bls_supervisor is not None:
            # the facade swap is process-global; one node owns it
            net.nodes[0].supervisor = bls_supervisor
        await net.start()
        try:
            last = args.epochs * net.spec.config.SLOTS_PER_EPOCH
            for slot in range(1, last + 1):
                await net.run_slot(slot)
                if slot % net.spec.config.SLOTS_PER_EPOCH == 0:
                    print(f"epoch {slot // net.spec.config.SLOTS_PER_EPOCH}"
                          f": justified={net.min_justified_epoch()} "
                          f"finalized={net.min_finalized_epoch()} "
                          f"converged={net.heads_converged()}")
            ok = (net.heads_converged()
                  and net.min_finalized_epoch() >= args.epochs - 3)
            print("devnet", "FINALIZED" if ok else "DID NOT FINALIZE")
            return 0 if ok else 1
        finally:
            await net.stop()

    rc = asyncio.run(run())
    _hard_exit_if_virtual_devices(rc)
    return rc


def cmd_transition(args) -> int:
    """Offline state transition over SSZ files (reference `transition`
    subcommand: cli/subcommand/TransitionCommand)."""
    from .spec import create_spec
    from .spec.transition import state_transition, StateTransitionError

    from .spec.codec import deserialize_signed_block, deserialize_state
    spec = create_spec(args.network)
    state = deserialize_state(spec.config, Path(args.pre).read_bytes())
    for blk_path in args.blocks:
        signed = deserialize_signed_block(spec.config,
                                          Path(blk_path).read_bytes())
        try:
            state = state_transition(spec.config, state, signed,
                                     validate_result=not args.no_validate)
        except StateTransitionError as exc:
            print(f"invalid block {blk_path}: {exc}", file=sys.stderr)
            return 1
    Path(args.post).write_bytes(type(state).serialize(state))
    print(f"post state written: slot={state.slot} root=0x"
          f"{state.htr().hex()}")
    return 0


def cmd_genesis(args) -> int:
    """Write an interop genesis state (reference `genesis` subcommand)."""
    from .spec import create_spec
    from .spec.genesis import interop_genesis

    spec = create_spec(args.network)
    state, _sks = interop_genesis(spec.config, args.validators,
                                  args.genesis_time)
    Path(args.out).write_bytes(spec.schemas.BeaconState.serialize(state))
    print(f"genesis written: {args.out} validators={args.validators} "
          f"root=0x{state.htr().hex()}")
    return 0


def cmd_migrate_database(args) -> int:
    """Convert a data dir between storage modes in place (reference
    cli/util/DatabaseMigrater.java + `migrate-database` subcommand).

    archive -> prune: drops per-slot state snapshots and the slot
    index (PRUNE serves only the anchor + hot subtree).
    prune -> archive: rebuilds the canonical slot index from the
    persisted finalized chain; intermediate states regenerate by
    replay on demand, so no state backfill is needed.
    """
    from .spec import create_spec
    from .storage.database import Database

    spec = create_spec(args.network)
    path = Path(args.data_dir) / "chain.db"
    if not path.exists():
        print(f"no database at {path}", file=sys.stderr)
        return 1
    db = Database(path, spec, mode=args.to)
    anchor_root = db._kv.get(b"meta/anchor_root")
    if anchor_root is None:
        print("database has no anchor; nothing to migrate",
              file=sys.stderr)
        db.close()
        return 1
    dropped_states = dropped_index = 0
    if args.to == "prune":
        for key in db._kv.keys_with_prefix(b"st/"):
            if key[len(b"st/"):] != anchor_root:
                db._kv.delete(key)
                dropped_states += 1
        for key in db._kv.keys_with_prefix(b"sl/"):
            db._kv.delete(key)
            dropped_index += 1
        print(f"migrated to prune: dropped {dropped_states} state "
              f"snapshots, {dropped_index} slot-index entries")
    else:
        db._index_finalized_chain(anchor_root)
        indexed = len(db._kv.keys_with_prefix(b"sl/"))
        print(f"migrated to archive: slot index rebuilt "
              f"({indexed} entries); states regenerate by replay")
    db.compact()
    db.close()
    return 0


def cmd_debug(args) -> int:
    """Debug helpers (reference cli/subcommand/debug/: DebugDbCommand,
    PrettyPrintCommand)."""
    from .spec import create_spec

    if args.debug_cmd == "pretty-print":
        from .spec.codec import (deserialize_signed_block,
                                 deserialize_state)
        spec = create_spec(args.network)
        raw = Path(args.file).read_bytes()
        if args.type == "state":
            obj = deserialize_state(spec.config, raw)
        else:
            obj = deserialize_signed_block(spec.config, raw)

        def render(v, indent=0):
            pad = "  " * indent
            if getattr(type(v), "_ssz_fields", None):
                lines = [f"{pad}{type(v).__name__}:"]
                for name in type(v)._ssz_fields:
                    lines.append(f"{pad}  {name}:")
                    lines.append(render(getattr(v, name), indent + 2))
                return "\n".join(lines)
            if isinstance(v, bytes):
                return f"{pad}0x{v.hex()}"
            if isinstance(v, (tuple, list)):
                if len(v) > 8:
                    return f"{pad}[{len(v)} items]"
                return "\n".join(render(x, indent) for x in v) \
                    if v else f"{pad}[]"
            return f"{pad}{v}"
        print(render(obj))
        return 0
    if args.debug_cmd == "db-info":
        from .storage.database import Database
        spec = create_spec(args.network)
        path = Path(args.data_dir) / "chain.db"
        if not path.exists():
            print(f"no database at {path}", file=sys.stderr)
            return 1
        db = Database(path, spec)
        prefixes = {b"blk/": "blocks", b"st/": "states",
                    b"hot/": "hot refs", b"sl/": "slot index",
                    b"bl/": "blob sidecars", b"meta/": "meta"}
        for prefix, label in prefixes.items():
            print(f"{label}: {len(db._kv.keys_with_prefix(prefix))}")
        anchor = db.load_anchor()
        if anchor is not None:
            print(f"anchor: slot={anchor[0].slot} "
                  f"root=0x{anchor[0].htr().hex()}")
        db.close()
        return 0
    print(f"unknown debug command {args.debug_cmd}", file=sys.stderr)
    return 1


def cmd_admin_weak_subjectivity(args) -> int:
    """Compute the weak-subjectivity period for a state (reference
    cli/subcommand/admin/WeakSubjectivityCommand)."""
    from .spec import create_spec
    from .spec.codec import deserialize_state
    from .spec.weak_subjectivity import (WeakSubjectivityValidator,
                                         compute_weak_subjectivity_period)

    spec = create_spec(args.network)
    state = deserialize_state(spec.config,
                              Path(args.state).read_bytes())
    period = compute_weak_subjectivity_period(spec.config, state)
    epoch = state.slot // spec.config.SLOTS_PER_EPOCH
    print(f"state epoch: {epoch}")
    print(f"weak subjectivity period: {period} epochs")
    print(f"safe until epoch: {epoch + period}")
    if args.current_epoch is not None:
        ok = WeakSubjectivityValidator(spec.config).is_within_period(
            state, args.current_epoch)
        print(f"within period at epoch {args.current_epoch}: {ok}")
        return 0 if ok else 2
    return 0


def cmd_slashing_protection(args) -> int:
    """EIP-3076 interchange import/export (reference
    slashing-protection subcommand)."""
    from .validator.slashing_protection import SlashingProtector

    protector = SlashingProtector(args.data_dir)
    gvr = bytes.fromhex(args.genesis_validators_root.removeprefix("0x"))
    if args.action == "export":
        doc = protector.export_interchange(gvr)
        Path(args.file).write_text(json.dumps(doc, indent=2))
        print(f"exported {len(doc['data'])} records")
    else:
        doc = json.loads(Path(args.file).read_text())
        n = protector.import_interchange(doc, gvr)
        print(f"imported {n} records")
    return 0


def cmd_voluntary_exit(args) -> int:
    """Sign and submit a voluntary exit through a beacon node's REST
    API (reference cli/subcommand/VoluntaryExitCommand.java): the exit
    epoch defaults to the chain's current epoch, the signature uses the
    interop key for --validator-index, and the node's pool validation
    is the acceptance gate."""
    import json as _json
    import urllib.error
    from .crypto import bls
    from .spec import create_spec
    from .spec import helpers as H
    from .spec.config import DOMAIN_VOLUNTARY_EXIT
    from .spec.datastructures import VoluntaryExit
    from .spec.genesis import interop_secret_keys
    from .spec.milestones import build_fork_schedule, SpecMilestone
    from .validator import RemoteValidatorApi

    if not 0 <= args.validator_index < args.interop_total:
        print(f"error: --validator-index must be in "
              f"[0, {args.interop_total})", file=sys.stderr)
        return 2
    spec = create_spec(args.network or "minimal")
    remote = RemoteValidatorApi(spec, args.beacon_node)
    try:
        genesis = remote._get_json("/eth/v1/beacon/genesis")["data"]
        gvr = bytes.fromhex(genesis["genesis_validators_root"][2:])
        if args.epoch is not None:
            epoch = args.epoch
        else:
            # the NODE's head decides "current": the local clock plus
            # a guessed preset can disagree with the node's config
            head = remote._get_json(
                "/eth/v1/beacon/headers/head")["data"]
            epoch = (int(head["header"]["message"]["slot"])
                     // spec.config.SLOTS_PER_EPOCH)
    except (urllib.error.URLError, OSError) as exc:
        print(f"error: beacon node unreachable: {exc}",
              file=sys.stderr)
        return 1
    msg = VoluntaryExit(epoch=epoch,
                        validator_index=args.validator_index)
    schedule = build_fork_schedule(spec.config)
    if schedule.milestone_at_epoch(epoch) >= SpecMilestone.DENEB:
        # EIP-7044: deneb+ pins exit domains to the capella fork so
        # exits stay valid forever (spec/deneb/block.py does the same
        # on the verification side)
        version = spec.config.CAPELLA_FORK_VERSION
    else:
        version = schedule.fork_at_epoch(epoch)[1]
    domain = H.compute_domain(DOMAIN_VOLUNTARY_EXIT, version, gvr)
    sk = interop_secret_keys(args.interop_total)[args.validator_index]
    signature = bls.sign(sk, H.compute_signing_root(msg, domain))
    body = _json.dumps({
        "message": {"epoch": str(epoch),
                    "validator_index": str(args.validator_index)},
        "signature": "0x" + signature.hex()}).encode()
    try:
        remote._post("/eth/v1/beacon/pool/voluntary_exits", body,
                     ctype="application/json")
    except urllib.error.HTTPError as exc:
        print(f"exit rejected: HTTP {exc.code} "
              f"{exc.read().decode(errors='replace')}", file=sys.stderr)
        return 1
    except (urllib.error.URLError, OSError) as exc:
        print(f"error: beacon node unreachable: {exc}",
              file=sys.stderr)
        return 1
    print(f"voluntary exit submitted: validator "
          f"{args.validator_index} at epoch {epoch}")
    return 0


def cmd_validator_client(args) -> int:
    """VC-only process: duties over the REST API of a remote beacon
    node (reference `validator-client` subcommand /
    ValidatorClientCommand.java with RemoteValidatorApiHandler)."""
    import time
    from .spec import create_spec
    from .spec.genesis import interop_secret_keys
    from .validator import (LocalSigner, RemoteValidatorApi,
                            SlashingProtectedSigner, ValidatorClient)
    from .validator.slashing_protection import SlashingProtector

    # the VC's hot path is signing (host-side); no background bring-up
    _configure_log_format(args, {})
    _configure_tracing(args, {})
    mont_path, mesh = _configure_kernel(args, {})
    _configure_bls(args, {}, supervise=False, mont_path=mont_path,
                   mesh=mesh)
    spec = create_spec(args.network or "minimal")
    remote = RemoteValidatorApi(spec, args.beacon_node)
    genesis = remote._get_json("/eth/v1/beacon/genesis")["data"]
    genesis_time = int(genesis["genesis_time"])
    sks = interop_secret_keys(args.interop_total)
    first = args.interop_start
    if first + args.interop_validators > args.interop_total:
        print("error: --interop-start + --interop-validators exceeds "
              "--interop-total", file=sys.stderr)
        return 2
    keys = {i: sks[i] for i in range(first,
                                     first + args.interop_validators)}
    signer = SlashingProtectedSigner(
        LocalSigner(keys),
        SlashingProtector(Path(args.data_dir) / "slashing"
                          if args.data_dir else None))
    client = ValidatorClient(spec, remote, signer, sorted(keys))
    print(f"validator client up: {len(keys)} validators "
          f"[{first}..{first + len(keys) - 1}] -> {args.beacon_node}")

    async def run():
        third = spec.config.SECONDS_PER_SLOT / 3
        while True:
            now = int(time.time())
            slot = max(0, (now - genesis_time)
                       // spec.config.SECONDS_PER_SLOT)
            if slot > 0:
                try:
                    await client.on_slot_start(slot)
                    await asyncio.sleep(third)
                    await client.on_attestation_due(slot)
                    await asyncio.sleep(third)
                    await client.on_aggregation_due(slot)
                except Exception:
                    logging.exception("duty loop error at slot %d", slot)
            next_slot_time = genesis_time + (slot + 1) * \
                spec.config.SECONDS_PER_SLOT
            await asyncio.sleep(max(0.1, next_slot_time - time.time()))

    asyncio.run(run())
    return 0


def cmd_peer(args) -> int:
    """Generate a node identity (reference `peer generate`)."""
    import secrets
    node_id = secrets.token_bytes(32)
    print(json.dumps({"node_id": node_id.hex()}))
    return 0


def cmd_loadgen(args) -> int:
    """Mainnet-shape load generator: replay seeded-deterministic
    gossip traffic (committee duplication, aggregation waves, sync
    committee, blob waves, adversarial storms) against the REAL
    signature service + admission controller on a virtual clock and
    print the per-scenario/per-class evidence."""
    from .loadgen import driver, scenarios

    if args.list:
        for name, sc in scenarios.SCENARIOS.items():
            print(f"{name:24s} {sc.description}")
        return 0
    names = (list(scenarios.DEFAULT_SWEEP) if args.scenario == "all"
             else [s.strip() for s in args.scenario.split(",")])
    for name in names:
        if name not in scenarios.SCENARIOS:
            print(f"unknown scenario {name!r}; known: "
                  f"{', '.join(scenarios.SCENARIOS)}", file=sys.stderr)
            return 2
    out = driver.run_scenarios(names, seed=args.seed, slots=args.slots,
                               validators=args.validators)
    if args.out:
        Path(args.out).write_text(json.dumps(out, indent=1))
    if args.json:
        print(json.dumps(out, indent=1))
    else:
        hdr = (f"{'scenario':24s} {'sigs/s':>8s} {'p50ms':>8s} "
               f"{'p99ms':>9s} {'dedup':>6s} {'sheds':>6s} "
               f"{'bisect':>6s} {'brownout':>8s}")
        print(hdr)
        for name, rep in out["scenarios"].items():
            print(f"{name:24s} {rep['sigs_per_sec']:>8.1f} "
                  f"{rep['p50_ms']:>8.1f} {rep['p99_ms']:>9.1f} "
                  f"{rep['dedup_ratio']:>6.2f} "
                  f"{rep['shed_total']:>6d} "
                  f"{rep['bisect_dispatches']:>6d} "
                  f"{rep['brownout']['enters']:>8d}")
        print("summary:", json.dumps(out["summary"]))
    summary = out["summary"]
    return 0 if summary["block_import_sheds_worst"] == 0 else 1


def _doctor_fetch_remote(base_url: str, last: int) -> dict:
    """Operator mode: read a LIVE node's admin endpoints and hand the
    snapshots to the engine — nothing here mutates the node."""
    import urllib.request

    def fetch(path):
        with urllib.request.urlopen(base_url.rstrip("/") + path,
                                    timeout=10) as resp:
            return json.loads(resp.read())

    out = {"records": [], "capacity": None, "slo": None,
           "flight": [], "admission": None, "mesh": None,
           "timeline": None}
    try:
        dispatches = fetch(f"/teku/v1/admin/dispatches?last={last}")
    except Exception as exc:  # noqa: BLE001 - operator-facing CLI
        raise SystemExit(
            f"doctor: cannot read {base_url.rstrip('/')}"
            f"/teku/v1/admin/dispatches ({exc}) — is the node up and "
            "does it serve the dispatch ledger?")
    out["records"] = dispatches.get("data", {}).get("records", [])
    try:
        out["capacity"] = fetch("/teku/v1/admin/capacity")["data"]
    except Exception:
        pass
    try:
        out["flight"] = fetch("/teku/v1/admin/flight_recorder").get(
            "data", [])
    except Exception:
        pass
    try:
        readiness = fetch("/teku/v1/admin/readiness")
        out["slo"] = readiness.get("slo")
        out["admission"] = readiness.get("admission")
        # the supervisor's mesh self-description (self_heal block):
        # keeps mesh_degraded diagnosable after the flight ring rolls
        out["mesh"] = (readiness.get("backend") or {}).get("mesh")
    except Exception:
        pass
    try:
        tl = fetch("/teku/v1/admin/timeline")
        out["timeline"] = {"traces": tl.get("traces") or [],
                           "events": tl.get("ring") or []}
    except Exception:
        pass
    return out


def _doctor_probe_devnet(args) -> dict:
    """Local mode: run a short LIVE in-process devnet on the REAL
    device provider (hard jax preflight — the whole point is that the
    ledger/capacity/SLO state being diagnosed is live dispatch
    evidence, not a stub), then harvest every diagnosis input."""
    from .node import Devnet
    from .crypto.bls import loader
    from .infra import capacity as cap
    from .infra import dispatchledger, flightrecorder, timeline, tracing

    mont_path, mesh = _configure_kernel(args, {})
    try:
        loader.configure(args.bls_impl or "jax", mont_path=mont_path,
                         mesh=mesh)
    except loader.BlsLoadError as exc:
        raise SystemExit(f"doctor probe: BLS preflight failed: {exc}")

    async def run():
        net = Devnet(n_nodes=args.nodes, n_validators=args.validators)
        await net.start()
        try:
            for slot in range(1, args.slots + 1):
                await net.run_slot(slot)
            node = net.nodes[0]
            slo = node.slo.snapshot() if node.slo is not None else None
            admission = (node.admission.snapshot()
                         if node.admission is not None else None)
            sup = getattr(node, "supervisor", None)
            mesh = sup.mesh if sup is not None else None
            return slo, admission, mesh
        finally:
            await net.stop()

    slo, admission, mesh = asyncio.run(run())
    # same clamp the admin endpoint applies: a zero/negative --last
    # must not flip records[-last:] into a head-drop
    return {"records": dispatchledger.LEDGER.snapshot(
                last=max(1, args.last)),
            "capacity": cap.snapshot(), "slo": slo,
            "flight": flightrecorder.RECORDER.snapshot(),
            "admission": admission, "mesh": mesh,
            "timeline": {"traces": tracing.slow_traces(),
                         "events": timeline.RING.snapshot()}}


def cmd_doctor(args) -> int:
    """Explainability engine over the dispatch decision ledger: WHY is
    the latency budget being spent the way it is — cold compiles per
    shape, mesh shard makespan skew, padding waste per lane bucket,
    H(m) cache coldness, brownouts/sheds/SLO burn — ranked, with every
    finding citing its evidence (dispatch records by seq + trace id,
    flight-recorder events).  Reads a live node via --url, or (default)
    runs a short live in-process devnet on the real device provider and
    diagnoses it."""
    from .infra import doctor

    _configure_log_format(args, {})
    _configure_tracing(args, {})
    _configure_overload(args, {})
    if args.url:
        inputs = _doctor_fetch_remote(args.url, args.last)
    else:
        inputs = _doctor_probe_devnet(args)
    diagnosis = doctor.diagnose(
        inputs["records"], capacity=inputs.get("capacity"),
        slo=inputs.get("slo"), flight_events=inputs.get("flight"),
        admission=inputs.get("admission"), mesh=inputs.get("mesh"),
        timeline=inputs.get("timeline"))
    if args.json:
        print(json.dumps(diagnosis, indent=1, default=str))
    else:
        print(doctor.render_text(diagnosis))
    if args.out:
        Path(args.out).write_text(
            json.dumps(diagnosis, indent=1, default=str))
    if not inputs["records"] and not args.url:
        # the local probe RAN a devnet: an empty ledger means the
        # device provider never dispatched — that is itself a defect
        print("doctor: probe produced no dispatch records",
              file=sys.stderr)
        return 1
    return 0


def cmd_timeline(args) -> int:
    """Unified causal timeline export (infra/timeline.py).  Joins the
    slow-trace ring, the dispatch decision ledger, the flight recorder
    and the timeline ring on the shared clock spine, then either
    resolves one trace id to its gap-free span tree (--trace-id) or
    writes the whole window as a Perfetto/Chrome trace-event file
    (--out trace.json — load in chrome://tracing or ui.perfetto.dev).
    Reads a live node via --url, or (default) runs a short live
    in-process devnet on the real device provider."""
    from .infra import schema, timeline

    _configure_log_format(args, {})
    _configure_tracing(args, {})
    _configure_overload(args, {})
    if args.url:
        inputs = _doctor_fetch_remote(args.url, args.last)
        tl = inputs.get("timeline") or {}
        traces, ring = tl.get("traces") or [], tl.get("events") or []
    else:
        inputs = _doctor_probe_devnet(args)
        tl = inputs["timeline"]
        traces, ring = tl["traces"], tl["events"]
    records = inputs.get("records") or []
    flight = inputs.get("flight") or []

    if args.trace_id:
        joined = timeline.join(
            args.trace_id, traces,
            [r for r in records
             if args.trace_id in (r.get("trace_ids") or [])],
            [e for e in flight
             if e.get("trace_id") == args.trace_id],
            [e for e in ring
             if e.get("trace_id") == args.trace_id])
        text = json.dumps(joined, indent=1, default=str)
        if args.out:
            Path(args.out).write_text(text)
        print(text)
        return 0 if joined["tree"] is not None else 1

    events = timeline.perfetto(traces, records, flight, ring)
    doc = schema.envelope("perfetto", {"traceEvents": events})
    if args.out:
        Path(args.out).write_text(json.dumps(doc, default=str))
    if args.json and not args.out:
        print(json.dumps(doc, default=str))
    else:
        tracks = sorted(e["args"]["name"] for e in events
                        if e["ph"] == "M" and e["name"] == "thread_name")
        print(f"timeline: {len(events)} trace events, "
              f"{len(traces)} trace(s), {len(records)} dispatch "
              f"record(s), tracks: {', '.join(tracks)}"
              + (f" -> {args.out}" if args.out else ""))
    # an export with nothing but track metadata means the probe saw no
    # dispatches at all — surface that the same way doctor does
    if not traces and not records and not args.url:
        print("timeline: probe produced no traces or dispatch records",
              file=sys.stderr)
        return 1
    return 0


def cmd_precompile(args) -> int:
    """Build the serving shape set into the AOT executable store
    (ops/shapeset.py enumerates it; infra/aotstore.py persists it).
    Install-time twin of supervisor WARMING: every program a boot of
    this config would compile is lowered+compiled HERE and serialized,
    so boots — and selfheal reshapes over the same device set — warm
    by deserializing in seconds instead of paying XLA.  Reports
    per-shape compile vs load (re-runs are incremental: valid entries
    are skipped as loads)."""
    import time as _time

    from .infra import aotstore, compilecache

    _configure_log_format(args, {})
    if args.store_dir:
        os.environ["TEKU_TPU_AOT_STORE_DIR"] = args.store_dir
    mont = str(args.mont_path).lower()
    if mont not in _MONT_PATHS:
        raise SystemExit(f"invalid --mont-path {mont!r} (use one of "
                         f"{'/'.join(_MONT_PATHS)})")
    os.environ["TEKU_TPU_MONT_MUL"] = mont
    mesh_choice = _validate_mesh(str(args.mesh).lower())
    os.environ["TEKU_TPU_MESH"] = mesh_choice
    mesh_n = (int(mesh_choice)
              if mesh_choice not in ("off", "auto") else 0)
    if mesh_n > 1:
        from .infra.env import ensure_virtual_devices
        ensure_virtual_devices(mesh_n)
    compilecache.configure()
    if aotstore.store_dir() is None:
        raise SystemExit("AOT store is off (TEKU_TPU_AOT_STORE / "
                         "TEKU_TPU_AOT_STORE_DIR) — nothing to build")

    from .ops import shapeset
    from .ops import verify as V
    from .ops.provider import JaxBls12381

    mesh_obj = None
    if mesh_n >= 2:
        from . import parallel
        mesh_obj = parallel.make_mesh(mesh_n, advertise=False)
    max_batch = args.max_batch or shapeset.SERVICE_MAX_BATCH
    min_bucket = args.min_bucket or shapeset.SERVICE_MIN_BUCKET
    # constructing the provider registers the pk_validate dispatcher;
    # staged_jits() registers the stage dispatchers; the mesh kernel
    # registers below
    impl = JaxBls12381(max_batch=max_batch,
                       min_bucket=min_bucket, mesh=mesh_obj)
    V.staged_jits()
    # a mainnet-preset node's aggregates: the bucket its supervisor
    # warms (`_key_bucket`), loaded from here at boot
    programs = list(shapeset.enumerate_programs(
        max_batch=max_batch, min_bucket=impl.min_bucket,
        key_bucket=shapeset.SERVICE_KEY_BUCKET,
        h2c_min_bucket=impl._h2c_min_bucket,
        group_cap=impl._group_cap, mesh=mesh_obj))
    print(f"precompile: {len(programs)} program(s) -> "
          f"{aotstore.store_dir()}")
    outcomes = {"compile": 0, "load": 0, "error": 0}
    t_all = _time.monotonic()
    for kernel, avals, meta in programs:
        if str(meta.get("stage")).startswith("mesh_"):
            impl._sharded.programs()
        disp = aotstore.dispatchers().get(kernel)
        if disp is None:
            print(f"  SKIP {kernel}: no registered dispatcher "
                  f"({meta})", file=sys.stderr)
            outcomes["error"] += 1
            continue
        t0 = _time.monotonic()
        try:
            outcome = disp.precompile(avals)
        except Exception as exc:
            print(f"  FAIL {kernel} {meta.get('shape', '')}: "
                  f"{type(exc).__name__}: {exc}", file=sys.stderr)
            outcomes["error"] += 1
            continue
        outcomes[outcome] += 1
        print(f"  {outcome:>7} {kernel:<28} "
              f"profile={meta.get('profile', '-'):<10} "
              f"{_time.monotonic() - t0:8.1f}s")
    print(f"precompile done in {_time.monotonic() - t_all:.1f}s: "
          f"{outcomes['compile']} compiled, {outcomes['load']} "
          f"already stored, {outcomes['error']} failed")
    return 1 if outcomes["error"] else 0


def cmd_lint(args) -> int:
    """tekulint: the AST-based invariant analyzer (teku_tpu/analysis).

    Mechanizes the review-hardening bug classes of PRs 1-12 — raw
    TEKU_TPU_* env reads, trace-time side effects inside jit'd
    kernels, torn two-read access to swap attributes, metric naming /
    label-vocabulary violations, undeclared fault sites and flight
    event kinds, duplicated private helpers, and README knob drift.
    Exit 0 = clean, 1 = unsuppressed findings (or stale suppression
    entries), 2 = the suppression file itself is invalid."""
    from .analysis import run_lint
    from .analysis.env_knob import render_knob_table
    from .analysis.suppress import SuppressionError

    try:
        report = run_lint(root=args.root,
                          suppressions_path=args.suppressions)
    except SuppressionError as exc:
        print(f"lint: {exc}", file=sys.stderr)
        return 2
    if args.knobs:
        table = render_knob_table(report.knobs)
        if args.out:
            Path(args.out).write_text(table + "\n")
        print(table)
        return 0
    if args.json:
        print(json.dumps(report.to_dict(), indent=1))
    else:
        print(report.render_text())
    if args.out:
        Path(args.out).write_text(
            json.dumps(report.to_dict(), indent=1))
    return 0 if report.clean else 1


# --------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="teku-tpu", description="TPU-native beacon node")
    sub = p.add_subparsers(dest="cmd", required=True)

    n = sub.add_parser("node", help="run a beacon node")
    n.add_argument("--network", default=None)
    n.add_argument("--config-file", default=None)
    n.add_argument("--p2p-port", type=int, default=None)
    n.add_argument("--rest-port", type=int, default=None)
    n.add_argument("--data-dir", default=None)
    n.add_argument("--storage-mode", default=None,
                   choices=["archive", "prune"],
                   help="archive keeps the full chain with state "
                        "snapshots; prune keeps finalized + hot")
    n.add_argument("--history-retention-epochs", type=int, default=None,
                   help="optionally drop finalized blocks/states older "
                        "than this many epochs (rolling-window node); "
                        "blob sidecars always prune at the DA window")
    n.add_argument("--interop-validators", type=int, default=None,
                   help="run the first N interop validators locally")
    n.add_argument("--interop-total", type=int, default=None,
                   help="total validators at genesis")
    n.add_argument("--genesis-time", type=int, default=None,
                   help="unix genesis time (default: now; devnet nodes "
                        "must agree)")
    n.add_argument("--udp-discovery-port", type=int, default=None,
                   help="enable UDP node discovery on this port "
                        "(0 = ephemeral)")
    n.add_argument("--bootnode", action="append",
                   help="UDP discovery bootstrap address ip:udp_port")
    n.add_argument("--peer", action="append",
                   help="host:port to dial (repeatable)")
    n.add_argument("--eth1-endpoint", default=None,
                   help="eth1 JSON-RPC host:port for the deposit "
                        "follower")
    n.add_argument("--eth1-follow-distance", type=int, default=None)
    n.add_argument("--checkpoint-sync-url", default=None,
                   help="REST base URL of a trusted node to anchor "
                        "from (finalized state + block)")
    n.add_argument("--bls-impl", default=None,
                   choices=["auto", "supervised", "jax", "pure"],
                   help="BLS provider: auto (= supervised) boots on "
                        "the pure oracle and hot-swaps to the JAX/TPU "
                        "kernel when background bring-up reaches READY; "
                        "jax blocks on a hard preflight and makes "
                        "accelerator failure fatal; pure opts out")
    n.add_argument("--mont-path", default=None,
                   choices=["vpu", "mxu", "auto"],
                   help="mont_mul engine for the verify kernels: vpu "
                        "(elementwise int64), mxu (int8 digit-split "
                        "matmul on the TPU matrix unit), auto "
                        "(default: vpu, until a chip measurement "
                        "earns mxu its place).  mxu on a non-TPU device "
                        "falls back to vpu with one warning.  Env: "
                        "TEKU_TPU_MONT_MUL")
    n.add_argument("--mesh", default=None, metavar="{off,auto,N}",
                   help="multi-chip verify mesh: off (default, "
                        "single-device dispatch), auto (largest pow-2 "
                        "<= available devices), or an explicit device "
                        "count N (non-pow-2/over-sized N demotes with "
                        "one warning; numeric N also forces N virtual "
                        "host devices on CPU fallback).  The "
                        "dedup-aware pipeline shards group-aligned: "
                        "each chip owns whole message groups.  Env: "
                        "TEKU_TPU_MESH")
    n.add_argument("--overload-control", default=None,
                   choices=["on", "off"],
                   help="adaptive batching + priority classes + "
                        "shed-by-class brownout under SLO feedback "
                        "(default on; thresholds via TEKU_TPU_BROWNOUT_"
                        "*/TEKU_TPU_ADMISSION_* env knobs)")
    n.add_argument("--tracing", default=None, choices=["on", "off"],
                   help="hot-path verify tracing: per-stage latency "
                        "histograms on /metrics and the slow-trace "
                        "ring on /teku/v1/admin/traces (default on; "
                        "off compiles spans to no-ops)")
    n.add_argument("--log-format", default=None,
                   choices=["text", "json"],
                   help="console log format: json emits one object "
                        "per line carrying the active trace id, so "
                        "logs correlate with slow traces and "
                        "flight-recorder events")
    n.set_defaults(fn=cmd_node)

    d = sub.add_parser("devnet", help="in-process fast devnet")
    d.add_argument("--nodes", type=int, default=2)
    d.add_argument("--validators", type=int, default=32)
    d.add_argument("--epochs", type=int, default=4)
    d.add_argument("--bls-impl", default=None,
                   choices=["auto", "supervised", "jax", "pure"])
    d.add_argument("--mont-path", default=None,
                   choices=["vpu", "mxu", "auto"])
    d.add_argument("--mesh", default=None, metavar="{off,auto,N}")
    d.add_argument("--tracing", default=None, choices=["on", "off"])
    d.add_argument("--overload-control", default=None,
                   choices=["on", "off"])
    d.add_argument("--log-format", default=None,
                   choices=["text", "json"])
    d.set_defaults(fn=cmd_devnet)

    t = sub.add_parser("transition", help="offline state transition")
    t.add_argument("--network", default="minimal")
    t.add_argument("--pre", required=True)
    t.add_argument("--post", required=True)
    t.add_argument("--no-validate", action="store_true")
    t.add_argument("blocks", nargs="*")
    t.set_defaults(fn=cmd_transition)

    g = sub.add_parser("genesis", help="write an interop genesis state")
    g.add_argument("--network", default="minimal")
    g.add_argument("--validators", type=int, default=64)
    g.add_argument("--genesis-time", type=int, default=1578009600)
    g.add_argument("--out", required=True)
    g.set_defaults(fn=cmd_genesis)

    s = sub.add_parser("slashing-protection",
                       help="EIP-3076 interchange import/export")
    s.add_argument("action", choices=["import", "export"])
    s.add_argument("--data-dir", required=True)
    s.add_argument("--file", required=True)
    s.add_argument("--genesis-validators-root", default="00" * 32)
    s.set_defaults(fn=cmd_slashing_protection)

    vc = sub.add_parser("validator-client",
                        help="VC-only process against a remote node")
    ve = sub.add_parser("voluntary-exit",
                        help="sign and submit a voluntary exit")
    ve.set_defaults(fn=cmd_voluntary_exit)
    ve.add_argument("--network", default=None)
    ve.add_argument("--beacon-node", default="http://127.0.0.1:5051")
    ve.add_argument("--validator-index", type=int, required=True)
    ve.add_argument("--epoch", type=int, default=None,
                    help="exit epoch (default: current)")
    ve.add_argument("--interop-total", type=int, default=64,
                    help="interop keyset size the index signs from")

    vc.add_argument("--network", default=None)
    vc.add_argument("--beacon-node", default="http://127.0.0.1:5051",
                    help="REST base URL of the beacon node")
    vc.add_argument("--interop-validators", type=int, default=8)
    vc.add_argument("--interop-start", type=int, default=0,
                    help="first interop key index this VC owns")
    vc.add_argument("--interop-total", type=int, default=64)
    vc.add_argument("--data-dir", default=None)
    vc.add_argument("--bls-impl", default=None,
                    choices=["auto", "supervised", "jax", "pure"])
    vc.add_argument("--mont-path", default=None,
                    choices=["vpu", "mxu", "auto"])
    vc.add_argument("--mesh", default=None, metavar="{off,auto,N}")
    vc.add_argument("--tracing", default=None, choices=["on", "off"])
    vc.add_argument("--log-format", default=None,
                    choices=["text", "json"])
    vc.set_defaults(fn=cmd_validator_client)

    pe = sub.add_parser("peer", help="generate a node identity")
    pe.set_defaults(fn=cmd_peer)

    lg = sub.add_parser(
        "loadgen",
        help="mainnet-shape load generator (virtual clock, real "
             "service + admission controller)")
    lg.add_argument("--scenario", default="all",
                    help="comma-separated scenario names, or 'all' "
                         "(see --list)")
    lg.add_argument("--list", action="store_true",
                    help="list known scenarios and exit")
    lg.add_argument("--seed", type=int, default=1,
                    help="traffic-model seed (same seed = identical "
                         "event stream)")
    lg.add_argument("--slots", type=int, default=2,
                    help="slots of traffic per scenario")
    lg.add_argument("--validators", type=int, default=None,
                    help="modeled network size (default 1,000,000)")
    lg.add_argument("--json", action="store_true",
                    help="print the full JSON report instead of the "
                         "table")
    lg.add_argument("--out", default=None,
                    help="also write the JSON report to this path")
    lg.set_defaults(fn=cmd_loadgen)

    dr = sub.add_parser(
        "doctor",
        help="explain the current latency budget from the dispatch "
             "decision ledger + capacity/SLO/flight-recorder state")
    dr.add_argument("--url", default=None,
                    help="base URL of a live node's REST API to "
                         "diagnose (e.g. http://127.0.0.1:5051); "
                         "default runs a short live in-process devnet "
                         "on the real device provider")
    dr.add_argument("--last", type=int, default=128,
                    help="how many ledger records to read")
    dr.add_argument("--json", action="store_true",
                    help="print the machine-readable diagnosis")
    dr.add_argument("--out", default=None,
                    help="also write the JSON diagnosis to this path")
    dr.add_argument("--slots", type=int, default=4,
                    help="probe devnet: slots to run")
    dr.add_argument("--nodes", type=int, default=1,
                    help="probe devnet: node count")
    dr.add_argument("--validators", type=int, default=8,
                    help="probe devnet: validator count")
    dr.add_argument("--bls-impl", default=None,
                    help="probe devnet BLS implementation (default "
                         "jax: the probe exists to exercise the real "
                         "device dispatch path)")
    dr.add_argument("--mont-path", default=None,
                    choices=list(_MONT_PATHS))
    dr.add_argument("--mesh", default=None,
                    help="probe devnet mesh spec (off, auto, or N)")
    dr.add_argument("--log-format", default=None,
                    choices=["text", "json"])
    dr.add_argument("--tracing", default=None)
    dr.add_argument("--overload-control", default=None)
    dr.set_defaults(fn=cmd_doctor)

    tl = sub.add_parser(
        "timeline",
        help="unified causal timeline: join traces + dispatch ledger "
             "+ flight recorder + timeline ring on one clock spine; "
             "export a Perfetto trace or resolve one trace id to its "
             "gap-free span tree")
    tl.add_argument("--url", default=None,
                    help="base URL of a live node's REST API (default "
                         "runs a short live in-process devnet on the "
                         "real device provider)")
    tl.add_argument("--trace-id", default=None,
                    help="resolve this trace id to its joined span "
                         "tree instead of exporting the whole window")
    tl.add_argument("--out", default=None,
                    help="write the Perfetto/Chrome trace-event JSON "
                         "(or joined tree) to this path")
    tl.add_argument("--json", action="store_true",
                    help="print the trace-event JSON to stdout")
    tl.add_argument("--last", type=int, default=128,
                    help="how many ledger records to read")
    tl.add_argument("--slots", type=int, default=4,
                    help="probe devnet: slots to run")
    tl.add_argument("--nodes", type=int, default=1,
                    help="probe devnet: node count")
    tl.add_argument("--validators", type=int, default=8,
                    help="probe devnet: validator count")
    tl.add_argument("--bls-impl", default=None,
                    help="probe devnet BLS implementation")
    tl.add_argument("--mont-path", default=None,
                    choices=list(_MONT_PATHS))
    tl.add_argument("--mesh", default=None,
                    help="probe devnet mesh spec (off, auto, or N)")
    tl.add_argument("--log-format", default=None,
                    choices=["text", "json"])
    tl.add_argument("--tracing", default=None)
    tl.add_argument("--overload-control", default=None)
    tl.set_defaults(fn=cmd_timeline)

    ln = sub.add_parser(
        "lint",
        help="AST-based invariant analyzer over the production tree "
             "(env-knob discipline, jit purity, torn reads, metric "
             "contract, closed registries, duplicate helpers, knob "
             "doc drift)")
    ln.add_argument("--root", default=None,
                    help="tree to analyze (default: this repo)")
    ln.add_argument("--suppressions", default=None,
                    help="suppression file (default: "
                         "<root>/lint_suppressions.json; every entry "
                         "needs a justification)")
    ln.add_argument("--json", action="store_true",
                    help="print the machine-readable report")
    ln.add_argument("--out", default=None,
                    help="also write the JSON report (or --knobs "
                         "table) to this path")
    ln.add_argument("--knobs", action="store_true",
                    help="emit the auto-extracted TEKU_TPU_* knob "
                         "registry as a markdown table and exit 0")
    ln.set_defaults(fn=cmd_lint)

    pc = sub.add_parser(
        "precompile",
        help="build the serving shape set into the AOT executable "
             "store (install-time compile: boots then warm by "
             "deserializing, not compiling)")
    pc.add_argument("--max-batch", type=int,
                    default=None, dest="max_batch",
                    help="service max batch (default: the service "
                         "tier's 256)")
    pc.add_argument("--min-bucket", type=int,
                    default=None, dest="min_bucket",
                    help="smallest lane bucket (default: the service "
                         "tier's 16)")
    pc.add_argument("--mesh", default="off",
                    help="mesh width to precompile for (off or N; "
                         "forces N virtual devices on CPU like `node "
                         "--mesh N`)")
    pc.add_argument("--mont-path", default="auto", dest="mont_path",
                    help="mont_mul engine "
                         f"({'/'.join(_MONT_PATHS)})")
    pc.add_argument("--store-dir", default=None, dest="store_dir",
                    help="AOT store directory (default: repo-adjacent "
                         ".jax_aot / TEKU_TPU_AOT_STORE_DIR)")
    pc.set_defaults(fn=cmd_precompile)

    mg = sub.add_parser("migrate-database",
                        help="convert a data dir between storage modes")
    mg.add_argument("--network", default="minimal")
    mg.add_argument("--data-dir", required=True)
    mg.add_argument("--to", required=True, choices=["archive", "prune"])
    mg.set_defaults(fn=cmd_migrate_database)

    dbg = sub.add_parser("debug", help="debug helpers")
    dbg_sub = dbg.add_subparsers(dest="debug_cmd", required=True)
    pp = dbg_sub.add_parser("pretty-print",
                            help="render an SSZ file as text")
    pp.add_argument("--network", default="minimal")
    pp.add_argument("type", choices=["state", "block"])
    pp.add_argument("file")
    di = dbg_sub.add_parser("db-info", help="database key statistics")
    di.add_argument("--network", default="minimal")
    di.add_argument("--data-dir", required=True)
    dbg.set_defaults(fn=cmd_debug)

    adm = sub.add_parser("admin", help="admin utilities")
    adm_sub = adm.add_subparsers(dest="admin_cmd", required=True)
    ws = adm_sub.add_parser("weak-subjectivity",
                            help="compute the WS period for a state")
    ws.add_argument("--network", default="minimal")
    ws.add_argument("--state", required=True)
    ws.add_argument("--current-epoch", type=int, default=None)
    ws.set_defaults(fn=cmd_admin_weak_subjectivity)
    return p


def main(argv=None) -> int:
    configure_logging()
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
