"""repro.serve — multi-tenant serving of frame programs over one shared engine."""
from .multitenant import MultiTenantServer, TenantProgram
