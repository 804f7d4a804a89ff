"""KvbcReplica — the process object wiring consensus + ledger + storage.

Rebuild of `concord::kvbc::Replica` (/root/reference/kvbc/include/Replica.h:42,
src/Replica.cpp): owns the DB backend, the categorized blockchain, the
consensus engine (whose persistent metadata lands in the same DB via
DBPersistentStorage), and the command handler that executes ordered
requests against the blockchain. The same inversion as the reference:
this object sits *above* the consensus engine it creates while also
implementing its execution upcall.
"""
from __future__ import annotations

import os
from typing import Optional

from tpubft.comm.interfaces import ICommunication
from tpubft.consensus.keys import ClusterKeys
from tpubft.consensus.replica import IRequestsHandler, Replica
from tpubft.kvbc.blockchain import KeyValueBlockchain
from tpubft.storage.interfaces import IDBClient
from tpubft.storage.memorydb import MemoryDB
from tpubft.storage.metadata import DBPersistentStorage
from tpubft.utils.config import ReplicaConfig
from tpubft.utils.metrics import Aggregator


def open_db(db_path: Optional[str],
            sync_writes: bool = False,
            sync_families=()) -> IDBClient:
    """Storage factory (reference: kvbc storage factories — RocksDB for
    production, memorydb for tests). `sync_writes` mirrors RocksDB
    WriteOptions.sync (reference leaves it false); `sync_families` keeps
    the named families fsync-durable regardless (the consensus-metadata
    carve-out)."""
    if db_path is None:
        return MemoryDB()
    from tpubft.storage.native import NativeDB
    os.makedirs(os.path.dirname(db_path) or ".", exist_ok=True)
    return NativeDB(db_path, sync_writes=sync_writes,
                    sync_families=sync_families)


class KvbcReplica:
    def __init__(self, cfg: ReplicaConfig, keys: ClusterKeys,
                 comm: ICommunication,
                 db_path: Optional[str] = None,
                 handler_factory=None,
                 aggregator: Optional[Aggregator] = None,
                 use_device_hashing: Optional[bool] = None,
                 thin_replica_port: Optional[int] = None) -> None:
        from tpubft.storage.metadata import CONSENSUS_META_FAMILIES
        self.db = open_db(
            db_path,
            sync_writes=getattr(cfg, "db_sync_writes", False),
            sync_families=(CONSENSUS_META_FAMILIES
                           if getattr(cfg, "db_sync_metadata", True)
                           else ()))
        from tpubft.kvbc import create_blockchain
        # resolve "auto" BEFORE the hashing decision below reads it (the
        # consensus Replica performs the same write-back; both orderings
        # must agree)
        from tpubft.crypto.backend import resolve_backend
        cfg.crypto_backend = resolve_backend(cfg.crypto_backend)
        if use_device_hashing is None:
            # device-backed crypto implies device-backed bulk hashing —
            # Merkle levels and block digests ride the batched SHA-256
            # kernel alongside the signature kernels
            use_device_hashing = cfg.crypto_backend == "tpu"
        self.blockchain = create_blockchain(
            self.db, version=getattr(cfg, "kvbc_version", "categorized"),
            use_device_hashing=use_device_hashing)
        if handler_factory is None:
            from tpubft.apps.skvbc import SkvbcHandler
            handler_factory = SkvbcHandler
        self.handler: IRequestsHandler = handler_factory(self.blockchain)
        from tpubft.consensus.reserved_pages import ReservedPages
        # pages share the LEDGER's DB on purpose: the execution lane
        # folds each run's reply-ring/marker pages into the ledger's
        # accumulated WriteBatch (ReservedPages.shares_db), so a run's
        # durable apply is atomic across blocks and at-most-once state —
        # a crash can never see blocks without their reply markers or
        # vice versa. Splitting pages into their own DB silently
        # downgrades that to two ordered batches.
        pages = ReservedPages(self.db)
        if thin_replica_port is not None:
            # the CLI port must win over cfg.thin_replica_port even
            # when thin_replica_enabled makes the Replica constructor
            # attach the server itself
            cfg.thin_replica_port = thin_replica_port
        self.replica = Replica(cfg, keys, comm, self.handler,
                               storage=DBPersistentStorage(self.db),
                               aggregator=aggregator,
                               reserved_pages=pages)
        # the merkle walk's read totals are process-wide (`kvbc`
        # component): served with this replica's own components
        from tpubft.kvbc import sparse_merkle
        self.replica.aggregator.register(sparse_merkle.METRICS)
        from tpubft.statetransfer import StateTransferManager
        from tpubft.statetransfer.manager import StConfig
        self.state_transfer = StateTransferManager(
            cfg.replica_id, self.blockchain,
            StConfig(fetch_batch_blocks=cfg.state_transfer_batch_blocks,
                     max_chunk_bytes=cfg.max_block_chunk_bytes,
                     window_ranges=cfg.st_window_ranges,
                     device_digest_threshold=cfg.st_device_digest_threshold,
                     use_device_digests=use_device_hashing),
            reserved_pages=pages, aggregator=aggregator)
        self.replica.set_state_transfer(self.state_transfer)
        from tpubft.reconfiguration.dispatcher import standard_dispatcher
        ckpt_dir = (os.path.join(os.path.dirname(db_path), "db_checkpoints")
                    if db_path else "db_checkpoints")
        self.replica.set_reconfiguration(standard_dispatcher(
            blockchain=self.blockchain, db=self.db,
            db_checkpoint_dir=ckpt_dir))

        # thin-replica read tier: the consensus Replica owns the server
        # (commit-stream feed + signed checkpoint anchor + metrics live
        # there). The explicit port arg (process CLI --trs-port) wins:
        # it is written into cfg BEFORE the Replica constructor runs
        # (see above), so a thin_replica_enabled config attaches at the
        # CLI port; without the knob, attach explicitly here.
        if thin_replica_port is not None \
                and self.replica.thin_replica is None:
            self.replica.attach_thin_replica(port=thin_replica_port)
        self.thin_replica_server = self.replica.thin_replica

    def start(self) -> None:
        self.replica.start()

    def stop(self) -> None:
        self.replica.stop()
        self.db.close()
