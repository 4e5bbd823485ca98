"""CNN training driver — port of the reference ``examples/cnn/main.py`` flow
to hetu_tpu (same flags, same Dataloader/Executor usage)."""
import argparse
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
import hetu_tpu as ht  # noqa: E402
import models  # noqa: E402

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger(__name__)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", type=str, required=True)
    parser.add_argument("--dataset", type=str, default="cifar10")
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--learning-rate", type=float, default=0.1)
    parser.add_argument("--opt", type=str, default="sgd")
    parser.add_argument("--num-epochs", type=int, default=10)
    parser.add_argument("--validate", action="store_true")
    parser.add_argument("--timing", action="store_true")
    parser.add_argument("--comm-mode", default=None,
                        help="None (single device) or allreduce/ps/hybrid (DP)")
    parser.add_argument("--json-out", default=None,
                        help="write final metrics as JSON (artifact path)")
    args = parser.parse_args()

    model = getattr(models, args.model.lower())
    opt = {
        "sgd": lambda: ht.optim.SGDOptimizer(args.learning_rate),
        "momentum": lambda: ht.optim.MomentumOptimizer(args.learning_rate),
        "nesterov": lambda: ht.optim.MomentumOptimizer(args.learning_rate,
                                                       nesterov=True),
        "adagrad": lambda: ht.optim.AdaGradOptimizer(
            args.learning_rate, initial_accumulator_value=0.1),
        "adam": lambda: ht.optim.AdamOptimizer(args.learning_rate),
    }[args.opt.lower()]()

    if args.dataset == "mnist":
        (tx, ty), (vx, vy), _ = ht.data.mnist()
        num_class = 10
    else:
        num_class = {"cifar10": 10, "cifar100": 100}[args.dataset]
        tx, ty, vx, vy = ht.data.normalize_cifar(num_class)
        if args.model == "mlp":
            tx, vx = tx.reshape(len(tx), -1), vx.reshape(len(vx), -1)

    x = ht.dataloader_op([ht.Dataloader(tx, args.batch_size, "train"),
                          ht.Dataloader(vx, args.batch_size, "validate")])
    y_ = ht.dataloader_op([ht.Dataloader(ty, args.batch_size, "train"),
                           ht.Dataloader(vy, args.batch_size, "validate")])
    loss, y = model(x, y_, num_class) if args.dataset == "cifar100" \
        else model(x, y_)
    train_op = opt.minimize(loss)

    eval_nodes = {"train": [loss, y, y_, train_op], "validate": [loss, y, y_]}
    strategy = ht.dist.DataParallel(args.comm_mode) if args.comm_mode else None
    executor = ht.Executor(eval_nodes, dist_strategy=strategy)

    n_train = executor.get_batch_num("train")
    n_valid = executor.get_batch_num("validate")
    logger.info("training %s on hetu_tpu (%s)", args.model,
                "DP" if strategy else "single-device")
    history = []
    for epoch in range(args.num_epochs):
        t0 = time.time()
        tl = []
        for _ in range(n_train):
            lv, *_ = executor.run("train")
            tl.append(float(lv.asnumpy()))
        entry = {"epoch": epoch, "train_loss": round(float(np.mean(tl)), 4)}
        msg = f"epoch {epoch}: train_loss={entry['train_loss']:.4f}"
        if args.validate:
            accs = []
            for _ in range(n_valid):
                _, pred, yv = executor.run("validate")
                accs.append(ht.metrics.accuracy(pred.asnumpy(), yv.asnumpy()))
            entry["val_acc"] = round(float(np.mean(accs)), 4)
            msg += f" val_acc={entry['val_acc']:.4f}"
        if args.timing:
            msg += f" ({time.time() - t0:.2f}s)"
        history.append(entry)
        logger.info(msg)
    if args.json_out:
        import json
        out = {"model": args.model, "dataset": args.dataset,
               "batch_size": args.batch_size, "opt": args.opt,
               "learning_rate": args.learning_rate,
               "epochs": args.num_epochs,
               "data_dir": os.environ.get("HETU_DATA_DIR"),
               "history": history, "final": history[-1] if history else {}}
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=1)
        logger.info("wrote %s", args.json_out)


if __name__ == "__main__":
    main()
