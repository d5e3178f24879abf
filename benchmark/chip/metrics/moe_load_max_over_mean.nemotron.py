"""``moe_load_max_over_mean`` in the ``nemotron_h`` cell: the largest over
the mean of the tokens routed to each of the router's 128 experts in the last
step, mean over the model's expert layers; 1 is even routing. The same
reading of the layers' ``load`` counters as the GLM cell's metric, under a
name of its own because the two cells' models, routers and learning rates
differ."""
import moe_load_max_over_mean


def read(run):
    return moe_load_max_over_mean.read(run)
