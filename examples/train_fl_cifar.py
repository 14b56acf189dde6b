"""End-to-end FL training driver (the paper's §VI protocol, full knobs).

Trains the paper's CIFAR CNN for a few hundred rounds with any
aggregation algorithm / attack combination, with periodic evaluation.
The CLI flags build one declarative ``repro.api.ExperimentSpec``; the
run record written next to the history IS the spec
(``spec.to_dict()``), so a run is reproducible from its own JSON:

    PYTHONPATH=src python examples/train_fl_cifar.py \
        --algorithm drag --rounds 200 --beta 0.1 --c 0.25
    PYTHONPATH=src python examples/train_fl_cifar.py \
        --algorithm br_drag --attack sign_flipping --malicious 0.3
"""
import argparse
import json
import os

from repro.api import (
    AggregationSpec,
    AttackSpec,
    DataSpec,
    ExperimentSpec,
    ModelSpec,
    SyncRegime,
    compile,
)

MODELS = {"emnist": "emnist_cnn", "cifar10": "cifar10_cnn", "cifar100": "cifar100_cnn"}


def build_spec(
    dataset: str = "cifar10",
    algorithm: str = "drag",
    rounds: int = 200,
    workers: int = 40,
    selected: int = 10,
    local_steps: int = 5,
    batch_size: int = 10,
    lr: float = 0.01,
    beta: float = 0.1,
    alpha: float = 0.25,
    c: float = 0.25,
    c_br: float = 0.5,
    attack: str = "none",
    malicious: float = 0.0,
    eval_every: int = 20,
    seed: int = 0,
) -> ExperimentSpec:
    return ExperimentSpec(
        data=DataSpec(
            dataset=dataset,
            n_workers=workers,
            beta=beta,
            malicious_fraction=malicious,
        ),
        model=ModelSpec(MODELS[dataset]),
        aggregation=AggregationSpec(
            algorithm=algorithm, alpha=alpha, c=c, c_br=c_br
        ),
        attack=AttackSpec(attack),
        regime=SyncRegime(
            rounds=rounds,
            n_selected=selected,
            local_steps=local_steps,
            batch_size=batch_size,
            lr=lr,
            eval_every=eval_every,
        ),
        seed=seed,
    )


def specs() -> list[tuple[str, ExperimentSpec]]:
    """Default spec (spec-matrix CI validation)."""
    return [("train_fl_cifar/default", build_spec(rounds=2, eval_every=1))]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cifar10", choices=sorted(MODELS))
    ap.add_argument("--algorithm", default="drag")
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--workers", type=int, default=40)
    ap.add_argument("--selected", type=int, default=10)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=10)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--beta", type=float, default=0.1)
    ap.add_argument("--alpha", type=float, default=0.25)
    ap.add_argument("--c", type=float, default=0.25)
    ap.add_argument("--c-br", type=float, default=0.5)
    ap.add_argument("--attack", default="none",
                    choices=["none", "noise_injection", "sign_flipping", "label_flipping"])
    ap.add_argument("--malicious", type=float, default=0.0)
    ap.add_argument("--eval-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="runs/fl")
    args = ap.parse_args()

    spec = build_spec(
        dataset=args.dataset,
        algorithm=args.algorithm,
        rounds=args.rounds,
        workers=args.workers,
        selected=args.selected,
        local_steps=args.local_steps,
        batch_size=args.batch_size,
        lr=args.lr,
        beta=args.beta,
        alpha=args.alpha,
        c=args.c,
        c_br=args.c_br,
        attack=args.attack,
        malicious=args.malicious,
        eval_every=args.eval_every,
        seed=args.seed,
    )
    os.makedirs(args.out, exist_ok=True)
    name = (f"{args.dataset}_{args.algorithm}_{args.attack}"
            f"_m{args.malicious}_b{args.beta}")

    def progress(m):
        print(f"round {m['round']:4d}  acc={m['accuracy']:.4f}", flush=True)

    hist = compile(spec).run(progress=progress)
    hist.pop("params")
    with open(os.path.join(args.out, name + ".json"), "w") as f:
        json.dump({"spec": spec.to_dict(), "history": hist}, f, indent=2)
    print(f"final accuracy: {hist['final_accuracy']:.4f} -> {args.out}/{name}.json")


if __name__ == "__main__":
    main()
