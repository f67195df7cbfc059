from .pipeline import (Request, field_rng, request_lengths,
                       synthetic_requests)
