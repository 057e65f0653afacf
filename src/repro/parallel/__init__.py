from .sharding import spatial_shardings, weighted_spatial_inputs
