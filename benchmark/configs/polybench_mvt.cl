// The user's kernels: PolyBench/GPU 1.0, OpenCL/MVT/mvt.cl (mvt_kernel1: the row walk, mvt_kernel2: the column walk of the same matrix), DATA_TYPE written out as float; both take the union of the source's two parameter lists (kernels that run in one compute share one array group).
__kernel void mvt_kernel1(__global float *a, __global float *x1, __global float *x2,
                          __global float *y1, __global float *y2, int n)
{
    int i = get_global_id(0);
    if (i < n)
    {
        int j;
        for (j = 0; j < n; j++)
        {
            x1[i] += a[i * n + j] * y1[j];
        }
    }
}
__kernel void mvt_kernel2(__global float *a, __global float *x1, __global float *x2,
                          __global float *y1, __global float *y2, int n)
{
    int i = get_global_id(0);
    if (i < n)
    {
        int j;
        for (j = 0; j < n; j++)
        {
            x2[i] += a[j * n + i] * y2[j];
        }
    }
}
