"""The network of the check's ``tiny_seq`` configuration as the program builds
it: a Gluon ``HybridBlock`` of ``nn.Embedding`` and ``nn.Dense`` layers, with
the masked cross-entropy in ``nd`` operators.  Entry ``block_step`` finds this
module by the configuration's ``network`` key."""
from mxnet_tpu import nd
from mxnet_tpu.gluon import HybridBlock, nn

N_INPUTS = 1            # of a batch's arrays, how many feed the block


class TinySeq(HybridBlock):
    def __init__(self, config, **kwargs):
        super().__init__(**kwargs)
        vocab, dim, ffn = config["vocab"], config["dim"], config["ffn"]
        with self.name_scope():
            self.embed = nn.Embedding(vocab, dim)
            self.gate = nn.Dense(ffn, in_units=dim, flatten=False,
                                 use_bias=False)
            self.up = nn.Dense(ffn, in_units=dim, flatten=False,
                               use_bias=False)
            self.down = nn.Dense(dim, in_units=ffn, flatten=False,
                                 use_bias=False)
            self.head = nn.Dense(vocab, in_units=dim, flatten=False)

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens)
        gate = self.gate(x)
        x = x + self.down(gate * F.sigmoid(gate) * self.up(x))
        return self.head(x)


def build(config):
    return TinySeq(config)


def loss(outputs, targets, mask):
    logp = nd.log_softmax(outputs[0].astype("float32"), axis=-1)
    return -nd.sum(nd.pick(logp, targets, axis=-1) * mask) / nd.sum(mask)
