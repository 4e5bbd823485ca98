"""MLP on MNIST — reference examples/cnn/main.py flow on hetu_tpu."""
import os, sys, time
import numpy as np
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import hetu_tpu as ht

datasets = ht.data.mnist()
(train_x, train_y), (valid_x, valid_y), _ = datasets
batch = 128

x = ht.dataloader_op([ht.Dataloader(train_x, batch, 'train'),
                      ht.Dataloader(valid_x, batch, 'validate')])
y_ = ht.dataloader_op([ht.Dataloader(train_y, batch, 'train'),
                       ht.Dataloader(valid_y, batch, 'validate')])

from hetu_tpu.layers import Linear, Sequence
model = Sequence(
    Linear(784, 256, activation='relu', name='fc1'),
    Linear(256, 10, name='fc2'),
)
logits = model(x)
loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(logits, y_), [0])
opt = ht.optim.MomentumOptimizer(learning_rate=0.05, momentum=0.9)
train_op = opt.minimize(loss)

executor = ht.Executor({'train': [loss, logits, y_, train_op],
                        'validate': [loss, logits, y_]})
n_train = executor.get_batch_num('train')
n_valid = executor.get_batch_num('validate')
print(f"devices={__import__('jax').devices()} train_batches={n_train}")

for epoch in range(3):
    t0 = time.time()
    tl = []
    for _ in range(n_train):
        lv, pred, yv, _ = executor.run('train')
        tl.append(float(lv.asnumpy()))
    accs, vls = [], []
    for _ in range(n_valid):
        lv, pred, yv = executor.run('validate')
        vls.append(float(lv.asnumpy()))
        accs.append(ht.metrics.accuracy(pred.asnumpy(), yv.asnumpy()))
    print(f"epoch {epoch}: train_loss={np.mean(tl):.4f} val_loss={np.mean(vls):.4f} "
          f"val_acc={np.mean(accs):.4f} ({time.time()-t0:.2f}s)")
