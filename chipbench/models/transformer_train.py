"""Builder of the ``transformer-base`` training programs, through the
repo's normal entry point (``paddle_tpu.models.transformer.build``)."""


def build(cfg, traffic):
    from paddle_tpu.models import transformer
    return transformer.build(
        src_vocab=cfg['src_vocab'], trg_vocab=cfg['trg_vocab'],
        max_len=int(traffic['length']), n_layer=cfg['n_layer'],
        n_head=cfg['n_head'], d_model=cfg['d_model'], d_ff=cfg['d_ff'],
        dropout=cfg['dropout'], lr=cfg['learning_rate'])


def vocab(cfg):
    return min(cfg['src_vocab'], cfg['trg_vocab'])


def feed(cfg, batch):
    return {'src_ids': batch['src'], 'trg_ids': batch['trg'],
            'lbl_ids': batch['next']}


def train_flops_per_token(cfg, traffic):
    """Operations one target token's training step needs: forward and
    backward (3 x forward), two per multiply-add, matrix products and
    attention only, nothing recomputed.  Source and target sequences are
    equally long, so the encoder's work per source token counts once per
    target token.  Causal self-attention needs half the score and value
    products of full attention, and is counted so."""
    d, ff, n, seq = cfg['d_model'], cfg['d_ff'], cfg['n_layer'], \
        int(traffic['length'])
    enc = n * (4 * d * d + 2 * d * ff + 2 * seq * d)
    dec = n * (8 * d * d + 2 * d * ff + seq * d + 2 * seq * d)
    head = d * cfg['trg_vocab']
    return 3.0 * 2.0 * (enc + dec + head)
