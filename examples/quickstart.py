"""Quickstart: data-free one-shot FL with DENSE in ~6 minutes on CPU.

Builds a 3-client non-IID federation on procedural image data, trains the
clients locally, uploads their models ONCE (the single communication round),
and runs DENSE's two server stages. Compares against one-shot FedAvg.

  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax

from repro.configs.paper_cifar import smoke
from repro.core import evaluate, train_dense_server
from repro.data import make_classification_data
from repro.fl import CommLedger, build_federation, fedavg
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    scfg = dataclasses.replace(smoke(), epochs=80, t_g=5, s_steps=8)
    print(f"federation: {scfg.n_clients} clients, Dirichlet α={scfg.alpha}")

    data = make_classification_data(
        0, num_classes=scfg.num_classes, size=scfg.image_size,
        ch=scfg.in_ch, train_per_class=scfg.train_per_class,
        test_per_class=scfg.test_per_class)
    xt, yt = data["test"]

    # --- the one and only communication round -------------------------
    ledger = CommLedger()
    clients, _ = build_federation(jax.random.PRNGKey(0), scfg, data,
                                  ledger=ledger)
    print(f"one-shot upload: {ledger.uplink_bytes/1e6:.2f} MB total, "
          f"{ledger.rounds} round, downlink={ledger.downlink_bytes} B")
    for i, c in enumerate(clients):
        print(f"  client{i}: n={c.n_data:4d} "
              f"local acc={evaluate(c.params, c.spec, xt, yt):.3f}")

    # --- baseline: parameter averaging ---------------------------------
    acc_avg = evaluate(fedavg(clients), clients[0].spec, xt, yt)
    print(f"one-shot FedAvg acc: {acc_avg:.3f}")

    # --- DENSE: generator stage + distillation stage -------------------
    stu, gen, hist = train_dense_server(jax.random.PRNGKey(1), clients, scfg)
    acc = evaluate(stu, clients[0].spec, xt, yt)
    print(f"DENSE global model acc: {acc:.3f}")
    print(f"generator losses (last epoch): "
          f"CE={hist.gen_parts[-1]['ce']:.3f} "
          f"BN={hist.gen_parts[-1]['bn']:.3f} "
          f"div={hist.gen_parts[-1]['div']:.3f}")


if __name__ == "__main__":
    main()
