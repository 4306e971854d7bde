"""Small cells: the real cells' files with the sizes cut so that a test
can run them on the CPU.  The equalizer keeps its cell's limits.  The
served model has limits of its own, set from its CPU readings (PERF.md):
a handful of short requests spread its gaps less than the full model's
long ones, so it is held tighter.

The served model keeps qwen3's layer (qk-norm, GQA, SwiGLU) at a
quarter of its width, four layers and a vocabulary of 8192, with the
weight scale doubled so that its logits spread as the full model's do
(RMS 0.64 = 0.04 x sqrt(256) = 0.02 x sqrt(1024)): the gaps then read
on the full model's scale.  The traffic is a handful of short requests
on four slots, whose steps are so short that every request is timed
for its time per output token, however little of the window it spans."""
from bench import spec


def serving_cell(name: str = "qwen3-0.6b.decode-heavy", **mix_changes):
    cell = spec.load_cell(name)
    cfg = dict(cell.config)
    cfg.update(vocab_size=8192, hidden_size=256, intermediate_size=768,
               num_hidden_layers=4, num_attention_heads=4,
               num_key_value_heads=2, head_dim=64, initializer_range=0.04)
    mix = dict(cell.traffic)
    mix.update(clients=4, block=8, prompt_grid=[8, 16],
               prompt_weights=[0.5, 0.5],
               output=dict(dist="lognormal", median=8, sigma=0.5, min=4,
                           max=16),
               engine=dict(max_slots=4, capacity=32), check_tokens=300,
               tpot_min_span_s=0.0,
               limits=dict(logit_gap=0.3, mean_gap=0.004))
    mix.update(mix_changes)
    return spec.Cell(name=cell.name, config_name=cell.config_name,
                     traffic_name=cell.traffic_name, chips=1, config=cfg,
                     traffic=mix, end_to_end=cell.end_to_end,
                     per_layer=cell.per_layer)


def equalizer_cell(**cfg_changes):
    cell = spec.load_cell("nr-fr2-400mhz.slot")
    cfg = dict(cell.config, subcarriers=24, slot_pool=3)
    cfg.update(cfg_changes)
    return spec.Cell(name=cell.name, config_name=cell.config_name,
                     traffic_name=cell.traffic_name, chips=1, config=cfg,
                     traffic=cell.traffic, end_to_end=cell.end_to_end,
                     per_layer=cell.per_layer)
