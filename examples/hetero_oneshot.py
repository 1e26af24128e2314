"""Heterogeneous one-shot FL (paper Table 2): every client has a DIFFERENT
architecture, so FedAvg is impossible — DENSE distills the mixed ensemble
into a server-chosen global model.

  PYTHONPATH=src python examples/hetero_oneshot.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

import jax

from repro.configs.paper_cifar import smoke
from repro.core import evaluate, train_dense_server
from repro.data import make_classification_data
from repro.fl import build_federation, fedavg
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    scfg = dataclasses.replace(
        smoke(), n_clients=3, client_kinds=("cnn1", "cnn2", "wrn16_1"),
        global_kind="wrn16_1", epochs=30, t_g=4, s_steps=6)
    data = make_classification_data(
        1, num_classes=scfg.num_classes, size=scfg.image_size,
        ch=scfg.in_ch, train_per_class=scfg.train_per_class,
        test_per_class=scfg.test_per_class)
    xt, yt = data["test"]
    clients, _ = build_federation(jax.random.PRNGKey(0), scfg, data)
    for c in clients:
        print(f"client arch={c.spec.kind:9s} n={c.n_data:4d} "
              f"acc={evaluate(c.params, c.spec, xt, yt):.3f}")

    try:
        fedavg(clients)
    except ValueError as e:
        print(f"FedAvg refuses (as it must): {e}")

    stu, _, _ = train_dense_server(jax.random.PRNGKey(1), clients, scfg)
    spec = dataclasses.replace(clients[0].spec, kind=scfg.global_kind)
    print(f"DENSE global ({scfg.global_kind}) acc: "
          f"{evaluate(stu, spec, xt, yt):.3f}")


if __name__ == "__main__":
    main()
