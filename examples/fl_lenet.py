"""End-to-end driver: federated LeNet-5 training over the TinyFL protocol.

The paper's full scenario (§IV-V): a server orchestrates microcontroller
clients over a simulated lossy 802.15.4/CoAP network; every message is
CBOR-encoded per Listings 1-3, CDDL-validated, block-wise transferred in
127 B frames; FedAvg aggregation; val<train stop condition; round
checkpointing with restart.

    PYTHONPATH=src python examples/fl_lenet.py [--rounds 5] [--clients 8]
"""
import argparse
import tempfile
from pathlib import Path

import jax

from repro import compile_cache
from repro.core.messages import ParamsEncoding
from repro.core.params_codec import flatten_params
from repro.data import partition_dirichlet, synthetic_mnist
from repro.fl import FLClient, FLServer, FLSimulation, OrchestrationConfig
from repro.models import lenet5
from repro.train.optim import SGDConfig
from repro.transport.network import LossyLink


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--samples-per-client", type=int, default=150)
    ap.add_argument("--drop-prob", type=float, default=0.05)
    ap.add_argument("--encoding", default="ta-float16le",
                    choices=[e.value for e in ParamsEncoding])
    ap.add_argument("--non-iid-alpha", type=float, default=1.0)
    args = ap.parse_args()
    compile_cache.enable(Path(__file__).resolve().parents[1])

    params = lenet5.init_params(jax.random.PRNGKey(0))
    flat, spec = flatten_params(params)
    print(f"LeNet-5: {flat.size} parameters "
          f"(paper Table II model, 44,426 expected)")

    data = synthetic_mnist(args.clients * args.samples_per_client, seed=0)
    shards = partition_dirichlet(data, args.clients,
                                 alpha=args.non_iid_alpha, seed=0)
    clients = [FLClient(i, shards[i], lenet5.loss_fn, spec,
                        local_epochs=1, batch_size=32, sgd=SGDConfig(lr=0.05),
                        dropout_prob=0.02)
               for i in range(args.clients)]

    with tempfile.TemporaryDirectory() as ckpt_dir:
        cfg = OrchestrationConfig(
            num_clients=args.clients, clients_per_round=args.clients,
            min_fraction=0.5, num_rounds=args.rounds, min_local_samples=32,
            params_encoding=ParamsEncoding(args.encoding),
            checkpoint_dir=ckpt_dir)
        server = FLServer(cfg, flat)
        sim = FLSimulation(server, clients, drop_prob=args.drop_prob)

        print(f"\n{'round':>5} {'train':>8} {'val':>8} {'reporters':>9} "
              f"{'dropped':>7} {'stopped':>7}")
        while not server.done:
            r = sim.run_round()
            print(f"{r.round:5d} {r.mean_train_loss:8.4f} "
                  f"{r.mean_val_loss:8.4f} {len(r.reporters):9d} "
                  f"{len(r.dropped):7d} {len(r.stopped):7d}")

        print("\n== per-message-type communication (all rounds) ==")
        for mtype, s in sorted(sim.accounting.by_type.items()):
            print(f"  {mtype:<26} {s.messages:4d} msgs {s.blocks:6d} blocks "
                  f"{s.frames:6d} frames {s.link_bytes:9d} B "
                  f"retx={s.retransmissions:4d} "
                  f"airtime={LossyLink.airtime_seconds(s):7.2f}s")
        ckpt = server.ckpt.latest()
        print(f"\nlatest round checkpoint: {ckpt.name} "
              f"({ckpt.stat().st_size} B, CBOR typed-array format)")


if __name__ == "__main__":
    main()
