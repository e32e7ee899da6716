"""Builder of the ``nmt-attn-512`` training programs, through the repo's
normal entry point (``paddle_tpu.models.seq2seq.build``)."""


def build(cfg, traffic):
    from paddle_tpu.models import seq2seq
    return seq2seq.build(
        src_dict_dim=cfg['src_dict_dim'], trg_dict_dim=cfg['trg_dict_dim'],
        embedding_dim=cfg['embedding_dim'],
        encoder_size=cfg['encoder_size'], decoder_size=cfg['decoder_size'],
        lr=cfg['learning_rate'])


def vocab(cfg):
    return min(cfg['src_dict_dim'], cfg['trg_dict_dim'])


def feed(cfg, batch):
    """Host LoD tensors, the reader's real form: the pipeline's staging
    thread pads and stages them."""
    import paddle_tpu.fluid as fluid
    rows, length = batch['src'].shape

    def lod(ids):
        return fluid.create_lod_tensor(ids.reshape(-1, 1), [[length] * rows])

    return {'src_word_id': lod(batch['src']),
            'target_language_word': lod(batch['trg']),
            'target_language_next_word': lod(batch['next'])}


def train_flops_per_token(cfg, traffic):
    """Operations one target token's training step needs (3 x forward, two
    per multiply-add, nothing recomputed), source and target equally long:
    encoder fc and LSTM recurrence, the encoder projection, the decoder's
    additive attention over ``length`` source positions, its input fc and
    GRU recurrence, and the dictionary-wide projection."""
    e, he, hd = cfg['embedding_dim'], cfg['encoder_size'], \
        cfg['decoder_size']
    seq = int(traffic['length'])
    enc = e * 4 * he + he * 4 * he + he * hd + he * hd / seq
    attn = hd * hd + seq * hd + seq * he
    dec = (he + e) * 3 * hd + hd * 3 * hd
    head = hd * cfg['trg_dict_dim']
    return 3.0 * 2.0 * (enc + attn + dec + head)
