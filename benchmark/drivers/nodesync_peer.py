"""One serving peer of the ``node-sync`` mix, in an OS process of its own:

    python3 benchmark/drivers/nodesync_peer.py HOME PARENT_PID HOST

The program's ``Node`` over the home directory ``drivers/nodesync.py`` made
(sqlite block and state stores holding the chain, ``config/genesis.json``),
built and started as the CLI's ``start`` builds and starts it, a dozen lines
and not the CLI because the parent needs three things of it: the p2p address
it listens on, on an ephemeral port (one line of JSON on standard output once
``Node.start()`` has returned), its death with the parent's (a parent-death
signal, and a watchdog on standard input for the case that signal is not to
be had), and a configuration that differs from ``Config()`` only in what the
cell's configuration states: listen addresses on the run's loopback address
(``loopback_of``), port 0, and the ``testnet`` command's two local-network
flags.

The parent gives this process ``JAX_PLATFORMS=cpu`` and
``TM_TPU_SKIP_WARMUP=1`` in its own environment: a peer verifies nothing and
must not open the chip, which belongs to the measured process.
"""

from __future__ import annotations

import ctypes
import json
import os
import signal
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PR_SET_PDEATHSIG = 1


def die_with_parent(parent: int) -> None:
    """SIGKILL when the parent dies (it reaches a stopped process too), and
    for a parent that died before the call, or a platform without prctl, a
    thread that ends the process when standard input closes."""
    try:
        ctypes.CDLL(None, use_errno=True).prctl(PR_SET_PDEATHSIG,
                                                signal.SIGKILL)
    except (OSError, AttributeError):
        pass
    if os.getppid() != parent:
        os._exit(0)

    def watch() -> None:
        try:
            sys.stdin.buffer.read()
        finally:
            os._exit(0)

    threading.Thread(target=watch, name="parent-watch", daemon=True).start()


def loopback_of(pid: int) -> str:
    """The loopback address of the run whose benchmark process is ``pid``:
    one of 127.0.0.0/8 that no other live process maps to. Every node of a
    run listens there, so two runs on one host share no port. On 127.0.0.1
    they did: an address book keeps the address of every node a peer has
    met, a stopped node's ephemeral port is soon given to a node of the run
    next door, that chain has the same id, and a dial is answered by whoever
    listens there (the program does not hold the answerer to the id it
    dialled; PERF.md section 7, PR 48): blocks of another seed's chain."""
    return "127.%d.%d.%d" % (1 + (pid // 64000) % 254, (pid // 250) % 256,
                             1 + pid % 250)


def local_config(home: str, persistent_peers: str = "",
                 host: str = "127.0.0.1"):
    """``Config()`` over ``home`` with what the cell's configuration states
    for every node of the network, the syncing one too: listen addresses on
    ``host`` port 0 (the benchmark gives every node of a run
    ``loopback_of`` its process; ``tests/test_node_sync.py`` starts nodes
    through this function too and keeps 127.0.0.1), and the ``testnet``
    command's two local-network flags."""
    from tendermint_tpu.config.config import Config

    cfg = Config().set_root(home)
    cfg.p2p.laddr = f"tcp://{host}:0"
    cfg.rpc.laddr = f"tcp://{host}:0"
    cfg.p2p.addr_book_strict = False
    cfg.p2p.allow_duplicate_ip = True
    cfg.p2p.persistent_peers = persistent_peers
    return cfg


def main(argv: list[str]) -> int:
    home, parent, host = argv[1], int(argv[2]), argv[3]
    die_with_parent(parent)

    from tendermint_tpu.node.node import Node, default_app

    node = Node(local_config(home, host=host), default_app("kvstore"))
    node.start()
    print(json.dumps({"ready": True, "p2p": node.p2p_addr(),
                      "id": node.node_key.id(), "pid": os.getpid(),
                      "height": node.block_store.height,
                      "base": node.block_store.base}), flush=True)
    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    while not done.wait(1.0):
        pass
    node.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
